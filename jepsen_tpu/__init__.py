"""jepsen_tpu: a TPU-native distributed-systems testing framework.

A ground-up rebuild of the capabilities of Jepsen (reference:
neuroradiology/jepsen): provision a cluster over SSH, drive concurrent client
operations from a pure generator DSL, inject faults with a nemesis layer,
record invocation/completion histories, and check those histories against
consistency models.

The differentiator is the analysis phase: histories are encoded as
HBM-resident integer tensors and checked by JAX kernels sharded across
a TPU mesh (Elle-style transactional anomaly search via MXU boolean
transitive closure; Knossos-style linearizability via batched frontier
search), so thousands of recorded runs can be verified in one batch.

Layer map (mirrors SURVEY.md section 1):
  control/    L0 remote control (SSH / dummy backends)
  os_setup    L1 environment provisioning + db.py DB lifecycle
  nemesis/    L2 fault injection
  client      L3 client protocol
  generator/  L4 pure generator DSL + interpreter
  core        L5 runner / orchestration
  checker/    L6 analysis (CPU oracles + TPU kernels)
  store       L7 persistence
  cli         L8 command line
  workloads/  L9 workload library
"""

__version__ = "0.1.0"
