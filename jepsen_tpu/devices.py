"""Device discovery for the analysis data plane.

The in-process JAX backend decides: `jax.devices()` (or the platform
`JEPSEN_TPU_PLATFORM` names) is what every kernel runs on. Nothing
here probes a backend in another process, re-pins the platform or
substitutes host CPU devices for a missing accelerator: a chip run
either runs on the chip or fails. Callers that want the virtual CPU
mesh ask for it (`JAX_PLATFORMS=cpu`, or `jax.devices("cpu")`).
"""

from __future__ import annotations

from . import gates


def default_devices() -> list:
    """The analysis devices: the `JEPSEN_TPU_PLATFORM` platform's when
    that gate is set, else the default backend's."""
    import jax

    plat = gates.get("JEPSEN_TPU_PLATFORM")
    return jax.devices(plat) if plat else jax.devices()


#: PCI vendor id of Google devices, and the class prefixes a TPU chip
#: reports: processing accelerator, or unassigned (v5e: 0x1ae0:0x0063,
#: class 0xff0000). Google's virtual NIC shares the vendor, not these.
_TPU_PCI_VENDOR = "0x1ae0"
_TPU_PCI_CLASSES = ("0x12", "0xff")


def host_chip_count(pci_root: str = "/sys/bus/pci/devices") -> int:
    """TPU chips attached to this host, counted from PCI without
    initialising a JAX backend — so a parent that must leave the chips
    to its children (the serve fleet's router) can size them."""
    from pathlib import Path

    n = 0
    for dev in Path(pci_root).glob("*"):
        try:
            n += ((dev / "vendor").read_text().strip() == _TPU_PCI_VENDOR
                  and (dev / "class").read_text().strip()
                  .startswith(_TPU_PCI_CLASSES))
        except OSError:
            pass
    return n


def device_platform(devices: list | None = None) -> str:
    """Platform of `devices` (default: the analysis devices)."""
    devs = devices if devices is not None else default_devices()
    return devs[0].platform if devs else "none"


def accelerator_available() -> bool:
    """True when the analysis devices are not host CPUs — the `auto`
    checker backend resolves to the device kernels exactly when this
    holds."""
    return device_platform() not in ("cpu", "none")


def resolve_backend(backend: str = "auto") -> str:
    """Resolve a checker backend choice to "tpu" (device kernels) or
    "cpu" (host oracles). "auto" — the default everywhere, mirroring the
    north star's `:backend :tpu` becoming the production analysis path —
    picks the device kernels when the in-process backend is an
    accelerator, else the CPU oracle. JEPSEN_TPU_BACKEND overrides the
    auto resolution (the CLI's --backend flag sets it; tests force the
    device path on the virtual CPU mesh with it)."""
    if backend == "race":
        # the engine race is implemented by Linearizable.check_batch
        # (which intercepts "race" before resolving); every other
        # checker treats it as "auto" — device when reachable
        backend = "auto"
    if backend != "auto":
        return backend
    env = gates.get("JEPSEN_TPU_BACKEND")
    if env and env not in ("auto", "race"):
        return env
    return "tpu" if accelerator_available() else "cpu"
