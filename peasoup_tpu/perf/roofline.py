"""Per-stage roofline accounting shared by BENCH and the microbench.

One stage classification — ``unpack / dedisperse / spectrum_chain / resample
/ harmonics / peaks / fold / other`` — classifies BOTH the profiler
trace's device events (tools/scope_trace stage_profile, driven by the
jit names and named scopes the drivers emit) and the registry's
programs (:func:`stage_for_program`), so a BENCH round and a
``peasoup-perf bench`` report attribute time to the SAME buckets and a
ratchet regression names the stage that moved.

Roofline fields: device-busy seconds and the trace's
``raw_bytes_accessed`` are MEASURED per stage; FLOPs are analytic
per-stage estimates supplied by the caller (bench.py derives them from
the run geometry). Against the device's peak FLOP/s and HBM bandwidth
(:func:`device_peaks` — datasheet numbers for the TPU generations the
fleet runs; conservative f32-MXU derates), each stage gets achieved
FLOP/s, achieved bytes/s, arithmetic intensity, the fraction of the
roofline it reaches, and whether the roofline says it is compute- or
memory-bound — the attribution that turns "the bench got slower" into
"the dedispersion stage fell off its bandwidth bound".
"""

from __future__ import annotations

STAGES = (
    "unpack",
    "dedisperse",
    "spectrum_chain",
    "resample",
    "harmonics",
    "peaks",
    "fdas",
    "fold",
    "other",
)

# program-name fragments -> stage, first match wins (checked against
# the full registered name, e.g. "ops.dedisperse.subband_stage1_matmul")
_PROGRAM_STAGE_RULES = (
    ("unpack", "unpack"),
    ("dedisperse", "dedisp"),
    # before "harmonic"/"correlate": the fused FDAS program contains
    # both fragments but books as its own MXU-correlation stage
    ("fdas", "fdas"),
    ("harmonics", "harmonic"),
    ("peaks", "peaks"),
    ("resample", "resample"),
    ("spectrum_chain", "spectrum."),
    ("spectrum_chain", "rednoise"),
    ("spectrum_chain", "zap"),
    ("spectrum_chain", "fft"),
    ("fold", "fold"),
    ("peaks", "singlepulse"),  # the sp chain ends in the peaks compaction
    ("peaks", "streaming"),
    ("peaks", "coincidence"),
    ("peaks", "correlate"),
    ("peaks", "ffa"),
)


def stage_for_program(name: str) -> str:
    """The roofline stage a registered program's time books under."""
    low = name.lower()
    for stage, frag in _PROGRAM_STAGE_RULES:
        if frag in low:
            return stage
    return "other"


# (device_kind substring, peak f32 FLOP/s, peak HBM bytes/s).
# Datasheet bf16 MXU peaks derated 4x for the f32 accumulate paths the
# pipeline runs (the MXU takes 4 passes for f32 operands); HBM numbers
# are the published per-chip bandwidths. Substring-matched against
# jax's device_kind so "TPU v5 lite" and "TPU v5e" both resolve.
_DEVICE_PEAKS = (
    ("v5p", 114e12, 2765e9),
    ("v5 lite", 49e12, 819e9),
    ("v5e", 49e12, 819e9),
    ("v6 lite", 230e12, 1640e9),
    ("v6e", 230e12, 1640e9),
    ("v4", 68e12, 1228e9),
    ("v3", 30e12, 900e9),
)


def device_peaks(device_kind: str) -> tuple[float, float] | None:
    """(peak f32 FLOP/s, peak HBM bytes/s) for a device kind, or None
    when unknown (CPU, new chips): roofline ratios then stay null
    rather than inventing a denominator."""
    low = (device_kind or "").lower()
    for frag, flops, bw in _DEVICE_PEAKS:
        if frag in low:
            return flops, bw
    return None


def roofline_fields(
    seconds: float,
    flops: float | None,
    nbytes: float | None,
    device_kind: str,
) -> dict:
    """The per-stage roofline record: achieved rates, arithmetic
    intensity, fraction-of-peak against the device roofline, and the
    bound the roofline model assigns. ``flops``/``bytes`` of None (or
    zero seconds) leave the derived fields null — absent attribution
    is visible, never faked."""
    out: dict = {
        "device_s": round(float(seconds), 6),
        "flops": None if flops is None else float(flops),
        "bytes": None if nbytes is None else float(nbytes),
        "achieved_flops_per_s": None,
        "achieved_bytes_per_s": None,
        "intensity_flops_per_byte": None,
        "peak_fraction": None,
        "bound": None,
    }
    if seconds <= 0:
        return out
    if flops:
        out["achieved_flops_per_s"] = round(flops / seconds, 3)
    if nbytes:
        out["achieved_bytes_per_s"] = round(nbytes / seconds, 3)
    if flops and nbytes:
        out["intensity_flops_per_byte"] = round(flops / nbytes, 6)
    peaks = device_peaks(device_kind)
    if peaks is None:
        return out
    peak_flops, peak_bw = peaks
    # the roofline: attainable FLOP/s at this intensity is
    # min(peak_flops, intensity * peak_bw); the binding resource is
    # whichever limit is lower
    if flops and nbytes:
        intensity = flops / nbytes
        ridge = peak_flops / peak_bw
        out["bound"] = "compute" if intensity >= ridge else "memory"
        attainable = min(peak_flops, intensity * peak_bw)
        out["peak_fraction"] = round((flops / seconds) / attainable, 4)
    elif nbytes:
        out["bound"] = "memory"
        out["peak_fraction"] = round((nbytes / seconds) / peak_bw, 4)
    elif flops:
        out["bound"] = "compute"
        out["peak_fraction"] = round((flops / seconds) / peak_flops, 4)
    return out


def stage_roofline(
    stage_profile: dict,
    stage_flops: dict | None,
    device_kind: str,
) -> dict:
    """Assemble the BENCH ``stages`` section: ``stage_profile`` maps
    stage -> (device seconds, measured bytes) from the trace
    (tools/scope_trace ScopeResult.stage_profile), ``stage_flops``
    maps stage -> analytic FLOPs (missing stages stay null)."""
    out = {}
    for stage, (secs, nbytes) in sorted(stage_profile.items()):
        flops = (stage_flops or {}).get(stage)
        out[stage] = roofline_fields(
            secs, flops, nbytes if nbytes else None, device_kind
        )
    return out
