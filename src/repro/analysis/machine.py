"""Named machine profiles: the single source of truth for hardware
constants.

Both the roofline analyzer (`repro.launch.roofline`) and the analytical
cost model (`repro.analysis.cost`) compose time estimates from the same
three roofline terms:

  compute_s    = FLOPs / peak_flops
  memory_s     = bytes / hbm_bw
  collective_s = collective_bytes / ici_bw

Before this module those constants lived (twice -- docstring and body) in
`launch/roofline.py`; now every consumer resolves a profile by name from
``MACHINES``, lumos-style: a small named-parameter table instead of
scattered literals.  Profiles are frozen dataclasses so a profile object
is hashable and safe to close over in cached model builders.

``dispatch_s`` models the fixed per-invocation launch/dispatch overhead
that floors the runtime of tiny regions: an approximation that removes
FLOPs but not invocations cannot beat ``t >= dispatch_s``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Union


@dataclasses.dataclass(frozen=True)
class MachineProfile:
    """Roofline parameters of one execution substrate."""

    name: str
    peak_flops: float        # FLOP/s per chip (bf16 for TPU profiles)
    hbm_bw: float            # bytes/s per chip
    ici_bw: float            # bytes/s per link
    dispatch_s: float = 0.0  # fixed per-invocation dispatch overhead

    def time_s(self, flops: float, bytes_: float = 0.0,
               coll_bytes: float = 0.0, invocations: float = 1.0) -> float:
        """Roofline time: max of the three terms, floored by dispatch."""
        t = max(flops / self.peak_flops,
                bytes_ / self.hbm_bw,
                coll_bytes / self.ici_bw)
        return t + invocations * self.dispatch_s


MACHINES: Dict[str, MachineProfile] = {
    # One TPU v5e chip, from Google Cloud's "TPU v5e" page: 197 TFLOP/s
    # bf16, 819 GB/s HBM, 1,600 Gbit/s of interconnect over 4 links. The
    # target substrate for roofline analysis and the default for cost
    # prediction.
    "tpu-v5e": MachineProfile(name="tpu-v5e", peak_flops=197e12,
                              hbm_bw=819e9, ici_bw=50e9,
                              dispatch_s=2e-6),
    # Host interpreter (CPU emulation of the techniques): orders of
    # magnitude slower, dispatch-dominated for small regions.  Used when
    # predicting for the host substrate so sub-1x overhead regimes (e.g.
    # oversized iACT tables) surface at realistic scales.
    "host-sim": MachineProfile(name="host-sim", peak_flops=100e9,
                               hbm_bw=40e9, ici_bw=10e9,
                               dispatch_s=20e-6),
}

DEFAULT_MACHINE = "tpu-v5e"

# `jax.Device.device_kind` -> profile name. A device that is not listed
# has no profile: `device_machine` raises rather than guess one.
DEVICE_KINDS: Dict[str, str] = {
    "TPU v5 lite": "tpu-v5e",
    "cpu": "host-sim",
}

# substrate name (repro.core.substrate) -> machine profile name
SUBSTRATE_MACHINES: Dict[str, str] = {
    "pallas": "tpu-v5e",
    "host": "host-sim",
}

# the calibrated profile's reserved name: get_machine("measured") measures
# the running backend on first use (see measure_machine)
MEASURED_MACHINE = "measured"


def measure_machine(name: str = MEASURED_MACHINE, *, size: int = 384,
                    copy_mb: int = 8, repeats: int = 3,
                    register: bool = True) -> MachineProfile:
    """Calibrate a roofline profile on the backend actually running.

    Three micro-measurements (median-of-k, warmed, blocked on results):

      peak_flops -- a jitted (size, size) f32 matmul: 2*size^3 FLOPs;
      hbm_bw     -- a jitted copy-scaled array op over ~copy_mb MiB
                    (read + write = 2x the buffer);
      dispatch_s -- a jitted scalar op: pure launch/dispatch floor.

    ``ici_bw`` is inherited from the static profile of the running
    substrate (interconnect bandwidth needs a multi-device collective to
    measure; single-host calibration cannot observe it). The result is
    registered in ``MACHINES`` under `name` so `AppCostModel(machine=
    "measured")`, ladder prescreens, and the kernel autotuner's pre-prune
    all sharpen to measured numbers instead of catalog constants.
    Committed tuning caches still key on the *static* profile names --
    "measured" is session-local by construction.
    """
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.obs.timing import measure

    def _med(fn, *args):
        return measure(fn, *args, warmup=1, repeats=max(1, repeats),
                       stat="median", span="machine.calibrate").seconds

    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(size, size).astype(np.float32))
    t_mm = _med(jax.jit(lambda x: x @ x), a)
    peak_flops = max(2.0 * size ** 3 / max(t_mm, 1e-9), 1e9)

    buf = jnp.asarray(rng.randn(copy_mb * (1 << 20) // 4)
                      .astype(np.float32))
    t_cp = _med(jax.jit(lambda x: x * 1.0000001 + 1.0), buf)
    hbm_bw = max(2.0 * buf.nbytes / max(t_cp, 1e-9), 1e8)

    dispatch_s = max(_med(jax.jit(lambda x: x + 1.0), jnp.float32(0.0)),
                     1e-7)

    base_name = device_machine()
    profile = MachineProfile(name=name, peak_flops=peak_flops,
                             hbm_bw=hbm_bw,
                             ici_bw=MACHINES[base_name].ici_bw,
                             dispatch_s=dispatch_s)
    if register:
        MACHINES[name] = profile
    return profile


def device_machine(device=None) -> str:
    """The profile name of `device` (default: the first JAX device), from
    its `device_kind`. An unlisted kind is a KeyError."""
    if device is None:
        import jax
        device = jax.devices()[0]
    kind = device.device_kind
    if kind not in DEVICE_KINDS:
        raise KeyError(
            f"no machine profile for device_kind {kind!r} (platform "
            f"{device.platform!r}); known kinds: {sorted(DEVICE_KINDS)}")
    return DEVICE_KINDS[kind]


def get_machine(machine: Union[str, MachineProfile, None] = None
                ) -> MachineProfile:
    """Resolve a profile by name (or pass one through). ``None`` gives the
    default profile; substrate names ("host" / "pallas") are accepted and
    mapped through ``SUBSTRATE_MACHINES``; ``"measured"`` calibrates the
    running backend on first use (`measure_machine`) and is cached in
    ``MACHINES`` for the rest of the process."""
    if machine is None:
        machine = DEFAULT_MACHINE
    if isinstance(machine, MachineProfile):
        return machine
    name = SUBSTRATE_MACHINES.get(machine, machine)
    if name == MEASURED_MACHINE and name not in MACHINES:
        return measure_machine()
    if name not in MACHINES:
        raise KeyError(
            f"unknown machine profile {machine!r} "
            f"(choose from: {', '.join(sorted(MACHINES))} "
            f"or '{MEASURED_MACHINE}')")
    return MACHINES[name]
