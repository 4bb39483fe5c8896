"""Per-backend capacity profiles for the planner passes.

One :class:`BackendProfile` per chip generation: usable HBM per device,
nominal interconnect bandwidth per mesh axis, peak matmul throughput, and
the lowering quirks the memory model must reproduce.  These are the
numbers the capacity planner (``memplan.py``/``commplan.py``) converts a
traced step program into "fits / does not fit" and "milliseconds on the
wire" with — and the seed of the backend capability probe ROADMAP item 3
asks for: everything here is a *declared* capability the dispatch tables
can eventually read instead of hard-coding platform checks.

Bandwidths are NOMINAL link rates (the public per-chip ICI/DCN figures,
not measured goodput); predicted times are therefore lower bounds — the
bench artifact's measured column is the calibration partner
(``bench_mfu_breakdown.json`` rows carry predicted + measured side by
side so the next chip session can fit a goodput factor).

Naming: ``<generation>-<devices>`` (``v4-8`` = a v4 slice of 8 devices),
matching the TPU pod-slice convention.  ``resolve`` accepts the bare
generation (``v4``) and defaults the device count to the current mesh.

This is also the ONE place a ``device_kind`` string is interpreted
(:data:`DEVICE_KIND_PROFILES`): the planner, ``bench.py``'s MFU
denominator and ``models/layers.py``'s attention thresholds all come here,
and a TPU kind with no row is an error, never a default.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class BackendProfile:
    """Declared capability sheet of one accelerator generation."""

    name: str
    #: usable HBM per device in GiB (the planner's default memory budget).
    #: Slightly under the marketing number: XLA reserves a slice for its
    #: runtime + collective scratch.
    hbm_gib: float
    #: nominal ICI bandwidth per device per mesh axis, GiB/s (one
    #: direction).  Collectives over in-slice axes (model/seq/pipe/data
    #: within a slice) ride this.
    ici_gibps: float
    #: nominal DCN bandwidth per host, GiB/s — the rate the ``data`` axis
    #: drops to when a mesh spans hosts over data-center network.
    dcn_gibps: float
    #: published peak dense bf16 TFLOP/s per device — ``bench.py``'s MFU
    #: denominator
    peak_bf16_tflops: float
    #: the most scoped VMEM one Pallas kernel may ask Mosaic for, MiB
    #: (``vmem_limit_bytes``; ``ops/pallas_attention.py stream_bwd_plan``
    #: is the one asker).  A share of the core's physical VMEM that leaves
    #: XLA's own fusions room; None = never tried on this generation, a
    #: kernel stays inside the compiler's default (16 MiB)
    kernel_vmem_mib: Optional[int] = None
    #: XLA-CPU lowering quirk: sub-fp32 (fp16/bf16) dot operands are
    #: materialized as fp32 copies because the host has no native
    #: half-precision GEMM.  The memory model must count those copies on
    #: CPU and must NOT count them on TPU.
    lowp_dot_f32_copies: bool = False
    #: runtime quirk: executables DESERIALIZED from the persistent
    #: compilation cache lose donated-buffer aliasing and compute garbage
    #: (observed on jax 0.4.x XLA-CPU — the resume-bench incident that
    #: introduced ``DSTPU_NO_DONATE``, docs/resilience.md).  On a
    #: quirk-listed backend the engine auto-skips donation whenever the
    #: persistent cache is enabled, and the compile-stability pass flags
    #: the combination (``stability.donation-cache-quirk``) if forced.
    persistent_cache_donation_unsafe: bool = False
    # ---- host-boundary cost constants (dispatchplan.py).  NOMINAL
    # figures, like the bandwidths above: the dispatch microbench
    # (``BENCH_DISPATCH=1`` → bench_dispatch.json) carries measured
    # columns next to these predictions so each rig can be calibrated.
    #: base host cost of launching ONE compiled program (runtime call +
    #: argument handling), microseconds
    dispatch_us: float = 100.0
    #: additional per-argument-leaf dispatch cost (pytree flattening +
    #: buffer table marshalling scale with the argument count)
    dispatch_leaf_us: float = 1.0
    #: host cost of one deliberate fence — a device round trip the host
    #: blocks on (``block_until_ready`` / scalar read), microseconds
    fence_us: float = 300.0
    #: host cost of one in-graph host-callback crossing (the telemetry
    #: spool drain), microseconds
    callback_us: float = 500.0
    #: host→device staging bandwidth, GiB/s (batch feeding, hyper
    #: staging — PCIe-class on real chips, memcpy on CPU)
    h2d_gibps: float = 10.0

    @property
    def hbm_bytes(self) -> int:
        return int(self.hbm_gib * (1 << 30))


#: Registry. HBM: usable = generation HBM minus ~1.3 GiB XLA runtime
#: reserve. ICI/DCN: public per-chip one-way figures for a 3D-torus slice
#: member (v4: 3 links x ~100 GB/s each is the all-links aggregate; the
#: per-axis number below is one link pair).  CPU: the tier-1 rig — HBM is
#: a host-RAM allowance per virtual device, "ICI" is shared memcpy.
#: v5e: 128 MiB of VMEM a core.  Mosaic compiled the streaming backward
#: under limits up to all 128 (AOT, libtpu 0.0.34); a kernel may ask for
#: three quarters (PERF.md §6, PR 36, has what the chip showed)
_V5E_KERNEL_VMEM_MIB = 96

PROFILES: Dict[str, BackendProfile] = {
    "v4-8": BackendProfile(
        name="v4-8", hbm_gib=30.75, ici_gibps=90.0, dcn_gibps=6.25,
        peak_bf16_tflops=275.0),
    "v5e-8": BackendProfile(
        name="v5e-8", hbm_gib=14.75, ici_gibps=45.0, dcn_gibps=6.25,
        peak_bf16_tflops=197.0,
        kernel_vmem_mib=_V5E_KERNEL_VMEM_MIB),
    "v5p-8": BackendProfile(
        name="v5p-8", hbm_gib=93.75, ici_gibps=150.0, dcn_gibps=6.25,
        peak_bf16_tflops=459.0),
    "cpu-8": BackendProfile(
        name="cpu-8", hbm_gib=4.0, ici_gibps=10.0, dcn_gibps=10.0,
        peak_bf16_tflops=1.0, lowp_dot_f32_copies=True,
        persistent_cache_donation_unsafe=True,
        # the rig of the v5e: a kernel runs here interpreted (the limit
        # means nothing) or is compiled ahead for a described v5e, so what
        # is traced here is the program that chip gets
        kernel_vmem_mib=_V5E_KERNEL_VMEM_MIB,
        # host == device: no PCIe hop, no device round trip.
        # CALIBRATED from this rig's bench_dispatch.json measured
        # columns (dispatch 3.657 µs, per-leaf 1.835 µs, fence 0.071 µs,
        # h2d 1.068 GiB/s — the old nominal guesses were 16×/420× off
        # and made every cpu dispatch-cost prediction fiction).
        # callback_us stays nominal: the microbench has no io_callback
        # leg yet.  Re-measure: BENCH_DISPATCH=1 python bench.py — the
        # leg now WARNS when measured/predicted drifts past 4×.
        dispatch_us=4.0, dispatch_leaf_us=1.8, fence_us=0.1,
        callback_us=200.0, h2d_gibps=1.0),
}

#: axes that cross DCN when the mesh spans hosts (docs/scaling.md: data
#: is the only axis that safely leaves the slice)
DCN_AXES = frozenset({"data"})


def resolve(name: str) -> BackendProfile:
    """Profile by name; bare generations default to the 8-device slice
    (``"v4"`` -> ``"v4-8"``)."""
    key = str(name).strip().lower()
    if key in PROFILES:
        return PROFILES[key]
    slice8 = f"{key}-8"
    if slice8 in PROFILES:
        return PROFILES[slice8]
    raise KeyError(
        f"unknown backend profile {name!r}; known: {sorted(PROFILES)}")


#: ``jax.devices()[0].device_kind`` → profile name.  A v5e reports
#: ``"TPU v5 lite"`` (jax 0.9.0 / libtpu 0.0.34, chip_smoke.py prints it)
DEVICE_KIND_PROFILES: Dict[str, str] = {
    "TPU v4": "v4-8",
    "TPU v5 lite": "v5e-8",
    "TPU v5e": "v5e-8",
    "TPU v5p": "v5p-8",
}


def for_device_kind(kind: str) -> BackendProfile:
    """Profile of the chip that reports ``kind``; an unknown kind raises
    rather than borrow another chip's numbers."""
    try:
        return PROFILES[DEVICE_KIND_PROFILES[kind]]
    except KeyError:
        raise KeyError(
            f"no backend profile for device kind {kind!r}; known kinds: "
            f"{sorted(DEVICE_KIND_PROFILES)} — add a row to "
            f"DEVICE_KIND_PROFILES / PROFILES in "
            f"deepspeed_tpu/analysis/profiles.py") from None


def default_profile() -> Optional[BackendProfile]:
    """Profile of the backend jax is actually running on.  On CPU this
    turns on the fp32-dot-copy quirk that makes predicted peaks comparable
    to ``compiled.memory_analysis()``; on TPU it is the attached chip's
    row (an unknown TPU kind raises); None on any other platform — the
    caller should then require an explicit ``--profile``."""
    import jax

    platform = jax.default_backend()
    if platform == "cpu":
        return PROFILES["cpu-8"]
    if platform == "tpu":
        return for_device_kind(jax.devices()[0].device_kind)
    return None
