"""TPU hardware database + analytic collective/compute cost model.

Reference parity: ``PerfUtils::{CalculateFlops, AllReduceCost, AllToAllCost,
AllGatherCost}`` (reference: service/parallel/performance_utils.{h,cc}) and the
V100/NVLink constants in ``Evaluator`` (parallel/evaluator.h:52-56). Here the
constants are per-TPU-generation (MXU TFLOPS, HBM GB/s, ICI GB/s per link,
DCN), and the collective formulas are the standard alpha-beta ring costs over
ICI — what XLA actually emits on TPU meshes.

Numbers are from public spec sheets / the public scaling literature
(jax-ml.github.io/scaling-book); they feed a *relative* cost model, so small
inaccuracies only matter if they flip a planning decision.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

from tepdist_tpu.core.service_env import ServiceEnv


@dataclasses.dataclass(frozen=True)
class TpuChipSpec:
    name: str
    bf16_tflops: float          # peak MXU bf16 TFLOP/s per chip
    hbm_gb: float               # HBM capacity per chip
    hbm_gbps: float             # HBM bandwidth GB/s
    ici_gbps_per_link: float    # unidirectional ICI bandwidth per link, GB/s
    ici_links: int              # ICI links per chip (torus degree)
    dcn_gbps: float             # per-host DCN bandwidth, GB/s


# Public TPU spec-sheet numbers.
TPU_CHIPS: Dict[str, TpuChipSpec] = {
    "v4": TpuChipSpec("v4", 275.0, 32.0, 1228.0, 50.0, 6, 25.0),
    "v5e": TpuChipSpec("v5e", 197.0, 16.0, 819.0, 50.0, 4, 25.0),
    "v5p": TpuChipSpec("v5p", 459.0, 95.0, 2765.0, 100.0, 6, 25.0),
    "v6e": TpuChipSpec("v6e", 918.0, 32.0, 1640.0, 100.0, 4, 25.0),
    # Virtual CPU target used by the test harness; tiny numbers keep the
    # planner's relative decisions realistic while making tests deterministic.
    "cpu": TpuChipSpec("cpu", 0.1, 8.0, 50.0, 1.0, 2, 1.0),
}


# ``jax.Device.device_kind`` of each chip in the table. The planner prices
# with the generation TPU_GENERATION names; measurement code (chip_smoke.py)
# looks the attached device up here so that a peak is never assumed for a
# chip the table does not describe.
DEVICE_KIND_GENERATION: Dict[str, str] = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5": "v5p",
    "TPU v6 lite": "v6e",
    "cpu": "cpu",
}


def chip_spec_for_device_kind(device_kind: str) -> TpuChipSpec:
    """The table row of an attached device (``jax.Device.device_kind``);
    an unknown kind is an error, not a default."""
    gen = DEVICE_KIND_GENERATION.get(device_kind)
    if gen is None:
        raise KeyError(
            f"unknown device_kind {device_kind!r}; known: "
            f"{list(DEVICE_KIND_GENERATION)}")
    return chip_spec(gen)


def chip_spec(generation: str | None = None) -> TpuChipSpec:
    gen = generation or ServiceEnv.get().tpu_generation
    spec = TPU_CHIPS.get(gen.lower())
    if spec is None:
        raise KeyError(f"unknown TPU generation {gen!r}; known: {list(TPU_CHIPS)}")
    env = ServiceEnv.get()
    if env.ici_bandwidth > 0 or env.dcn_bandwidth > 0 or env.hbm_gb > 0:
        spec = dataclasses.replace(
            spec,
            ici_gbps_per_link=(env.ici_bandwidth if env.ici_bandwidth > 0
                               else spec.ici_gbps_per_link),
            dcn_gbps=(env.dcn_bandwidth if env.dcn_bandwidth > 0
                      else spec.dcn_gbps),
            hbm_gb=(env.hbm_gb if env.hbm_gb > 0 else spec.hbm_gb),
        )
    return spec


GB = 1e9
# Fixed per-collective launch latency (the "alpha" term), seconds. ICI hops
# are ~1us; XLA fuses/overlaps, so a small constant suffices for ranking.
ALPHA_S = 2e-6

# Wire-byte shrink factor per communication dtype relative to f32 payloads.
# "" / "float32" = fidelity (no compression). The evaluator prices every
# gradient collective once per dtype and the argmin decides per candidate
# (EQuARX, arXiv:2506.17615: quantized AllReduce at ~2x inside XLA).
COMM_DTYPE_RATIOS: Dict[str, float] = {
    "": 1.0,
    "float32": 1.0,
    "bfloat16": 0.5,
    "int8": 0.25,
}

# Optimizer-state bytes per gradient byte (ZeRO pricing, arXiv:2004.13336).
# Adam keeps two fp32 moments per fp32 param, so the state is ~2x the
# param/grad payload; SGD-with-momentum is 1x and plain SGD 0x, but the
# planner prices the worst common case — over-estimating state for a
# stateless optimizer only makes a feasible plan look tighter, never
# flips a ranking between two candidates (both carry the same factor).
OPT_STATE_FACTOR = 2.0


def param_wire_dtype(comm_dtype: str) -> str:
    """Wire dtype for the ZeRO updated-param all-gather under a comm-dtype
    modifier. Gradients tolerate int8 fake-quant (stochastic rounding keeps
    the expectation), but PARAMS quantized to int8 every step would
    accumulate bias directly into the weights — so int8 plans gather params
    at bf16, the asymmetry EQuARX also keeps."""
    if comm_dtype == "int8":
        return "bfloat16"
    return comm_dtype


def _calib():
    """The active calibration profile (telemetry/calibrate.py) or None.
    Lazy import: calibrate has no module-level dependency on this module,
    but keeping the import inside the call avoids any telemetry<->parallel
    import cycle and costs one cached-module lookup."""
    from tepdist_tpu.telemetry.calibrate import active_profile
    return active_profile()


class PerfUtils:
    """Alpha-beta ring-cost formulas over an ICI axis of ``n`` chips.

    All costs in seconds for ``bytes_`` payload per participating chip. The
    ring formulas match what XLA emits for 1D ICI axes: reduce-scatter +
    all-gather for all-reduce, neighbor exchanges for all-to-all.
    """

    @staticmethod
    def _bw(spec: TpuChipSpec, over_dcn: bool) -> float:
        prof = _calib()
        if prof is not None and prof.ar_bytes_per_s > 0:
            # Measured ring bandwidth replaces the spec-sheet link math —
            # the profile already folds in topology and software overhead.
            return prof.ar_bytes_per_s
        # Bidirectional ring: 2 links usable per axis direction on a torus.
        return (spec.dcn_gbps if over_dcn else 2.0 * spec.ici_gbps_per_link) * GB

    @classmethod
    def all_reduce_cost(cls, bytes_: float, n: int, spec: TpuChipSpec | None = None,
                        over_dcn: bool = False) -> float:
        if n <= 1:
            return 0.0
        spec = spec or chip_spec()
        bw = cls._bw(spec, over_dcn)
        return ALPHA_S * (n - 1) + 2.0 * bytes_ * (n - 1) / (n * bw)

    @classmethod
    def all_gather_cost(cls, bytes_: float, n: int, spec: TpuChipSpec | None = None,
                        over_dcn: bool = False) -> float:
        """``bytes_`` = full (gathered) size."""
        if n <= 1:
            return 0.0
        spec = spec or chip_spec()
        bw = cls._bw(spec, over_dcn)
        return ALPHA_S * (n - 1) + bytes_ * (n - 1) / (n * bw)

    reduce_scatter_cost = all_gather_cost  # identical ring cost shape

    @classmethod
    def all_to_all_cost(cls, bytes_: float, n: int, spec: TpuChipSpec | None = None,
                        over_dcn: bool = False) -> float:
        """``bytes_`` = per-chip resident size; each chip keeps 1/n, sends the
        rest. On a bidirectional ring the bisection limits throughput to
        ~bytes*(n/4)/bw; use the exact ring formula bytes*(n^2-1)/(4n)/bw
        ~= bytes*n/4 for large n."""
        if n <= 1:
            return 0.0
        spec = spec or chip_spec()
        bw = cls._bw(spec, over_dcn)
        return ALPHA_S * (n - 1) + bytes_ * (n * n - 1) / (4.0 * n * bw)

    @classmethod
    def ppermute_cost(cls, bytes_: float, spec: TpuChipSpec | None = None,
                      over_dcn: bool = False) -> float:
        """One neighbor hop (ring attention / pipeline send-recv)."""
        prof = _calib()
        if prof is not None and prof.transfer_bytes_per_s > 0:
            return ALPHA_S + bytes_ / prof.transfer_bytes_per_s
        spec = spec or chip_spec()
        return ALPHA_S + bytes_ / (spec.ici_gbps_per_link * GB if not over_dcn
                                   else spec.dcn_gbps * GB)

    @classmethod
    def compute_time(cls, flops: float, spec: TpuChipSpec | None = None,
                     mxu_util: float = 0.5) -> float:
        spec = spec or chip_spec()
        t = flops / (spec.bf16_tflops * 1e12 * mxu_util)
        prof = _calib()
        if prof is not None and prof.compute_scale > 0:
            t *= prof.compute_scale
        return t

    @classmethod
    def hbm_time(cls, bytes_: float, spec: TpuChipSpec | None = None) -> float:
        spec = spec or chip_spec()
        t = bytes_ / (spec.hbm_gbps * GB)
        prof = _calib()
        if prof is not None and prof.hbm_scale > 0:
            t *= prof.hbm_scale
        return t

    # -- compressed collectives (comm-dtype candidate modifiers) ----------
    @classmethod
    def quantize_overhead(cls, bytes_: float, comm_dtype: str,
                          spec: TpuChipSpec | None = None) -> float:
        """Quantize + dequantize compute term per participating tensor,
        modeled as HBM passes over the fidelity payload: one read + one
        write on each side for the cast, plus one extra read for int8's
        per-chunk max-abs scale pass. Element-wise, so bandwidth-bound —
        never MXU-bound."""
        ratio = COMM_DTYPE_RATIOS.get(comm_dtype, 1.0)
        if ratio >= 1.0 or bytes_ <= 0:
            return 0.0
        passes = 2.0 if comm_dtype != "int8" else 3.0
        return 2.0 * cls.hbm_time(passes * bytes_, spec)

    @classmethod
    def compressed_all_reduce_cost(
            cls, bytes_: float, n: int, comm_dtype: str,
            spec: TpuChipSpec | None = None,
            over_dcn: bool = False) -> float:
        """Ring all-reduce over the SHRUNK wire bytes plus the
        quantize/dequantize term; degenerates to the fidelity cost for
        ""/float32."""
        ratio = COMM_DTYPE_RATIOS.get(comm_dtype, 1.0)
        return (cls.all_reduce_cost(bytes_ * ratio, n, spec, over_dcn)
                + cls.quantize_overhead(bytes_, comm_dtype, spec))

    @classmethod
    def compressed_all_gather_cost(
            cls, bytes_: float, n: int, comm_dtype: str,
            spec: TpuChipSpec | None = None,
            over_dcn: bool = False) -> float:
        ratio = COMM_DTYPE_RATIOS.get(comm_dtype, 1.0)
        return (cls.all_gather_cost(bytes_ * ratio, n, spec, over_dcn)
                + cls.quantize_overhead(bytes_, comm_dtype, spec))

    @classmethod
    def zero_update_cost(cls, grad_bytes: float, dp: int, comm_dtype: str,
                         spec: TpuChipSpec | None = None,
                         over_dcn: bool = False) -> float:
        """ZeRO-1 weight-update collectives over a DP axis of ``dp``
        (arXiv:2004.13336): reduce-scatter the accumulated gradient, apply
        on the local 1/dp shard, all-gather the updated params. Composes
        with the comm-dtype modifier on BOTH collectives (grads at
        ``comm_dtype``, params at :func:`param_wire_dtype`). Note
        RS + AG at equal bytes = ring AR + one extra alpha sweep, so ZeRO
        never wins on pure seconds — it wins by making optimizer state
        1/dp per device (memory feasibility)."""
        if dp <= 1:
            return 0.0
        rs_ratio = COMM_DTYPE_RATIOS.get(comm_dtype, 1.0)
        ag_dtype = param_wire_dtype(comm_dtype)
        ag_ratio = COMM_DTYPE_RATIOS.get(ag_dtype, 1.0)
        return (cls.reduce_scatter_cost(grad_bytes * rs_ratio, dp, spec,
                                        over_dcn)
                + cls.quantize_overhead(grad_bytes, comm_dtype, spec)
                + cls.all_gather_cost(grad_bytes * ag_ratio, dp, spec,
                                      over_dcn)
                + cls.quantize_overhead(grad_bytes, ag_dtype, spec))

    @classmethod
    def compressed_ppermute_cost(
            cls, bytes_: float, comm_dtype: str,
            spec: TpuChipSpec | None = None,
            over_dcn: bool = False) -> float:
        """One neighbor hop on the shrunk wire (pipeline SEND/RECV with a
        compressed activation payload)."""
        ratio = COMM_DTYPE_RATIOS.get(comm_dtype, 1.0)
        return (cls.ppermute_cost(bytes_ * ratio, spec, over_dcn)
                + cls.quantize_overhead(bytes_, comm_dtype, spec))
