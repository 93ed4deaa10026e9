"""The Fourier-domain acceleration-search (FDAS) device program.

Where the time-domain path (pipeline/accel_search.py) re-resamples
and re-FFTs the time series once per acceleration trial, FDAS forms
ONE dereddened/zapped spectrum per DM trial and recovers every
(f-dot, f-ddot) trial by correlating that spectrum against a bank of
finite-duration response templates (peasoup_tpu/fdas/templates.py) —
batched complex multiplies in the frequency domain, an MXU-friendly
shape. The whole (DM block x template batch) tile is one jitted
program: overlap-save correlation, interbin power, normalisation,
harmonic summing and per-level peak compaction stay fused; Python
only ever sees static-size peak sets.

Template rows are independent: a row's output depends on that row
alone. The OOM ladder in pipeline/fdas.py halves the template batch
under device pressure; the search's peak sets stay bitwise equal
(tests/test_fdas.py pins this), while the raw correlation of a split
bank agrees with the unsplit one to f32 rounding, since XLA's CPU FFT
rounds per batch shape.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .harmonics import harmonic_sums
from .peaks import cluster_peaks_device, find_peaks_device
from .rednoise import whiten_fseries
from .spectrum import form_interpolated, normalise, spectrum_stats
from .zap import zap_birdies


class FdasPeaks(NamedTuple):
    """Static-size peak sets for a block of DM trials.

    idxs/snrs: (D, nharms+1, T, max_peaks) — level 0 is the template
    correlation power itself, level h the 2^h-harmonic sum; T indexes
    the template (f-dot/f-ddot trial) batch. counts: (D, nharms+1, T)
    raw threshold crossings (overflow signal); ccounts the min-gap
    cluster counts actually compacted into idxs/snrs.
    """

    idxs: jax.Array
    snrs: jax.Array
    counts: jax.Array
    ccounts: jax.Array


def _pad_trial(tim, *, size, nsamps_valid):
    """Pad/truncate one trial to ``size`` with the mean-padded tail
    (same formula as pipeline/accel_search.py — ops/ must not import
    pipeline/, so the three lines are duplicated, pinned equal by the
    z=0 parity test)."""
    x = tim[:size].astype(jnp.float32)
    if nsamps_valid < size:
        x = jnp.pad(x, (0, size - x.shape[0]))
        mean_head = jnp.mean(x[:nsamps_valid])
        idx = jnp.arange(size)
        x = jnp.where(idx < nsamps_valid, x, mean_head)
    return x


# FFT-batch row alignment: every batched FFT inside correlate_bank
# runs over a template axis padded to this multiple, so the flattened
# transform count is lane-aligned for ANY template-batch size and no
# data row goes through the backend's vector-remainder path.
_ROW_ALIGN = 8


def correlate_bank(fser, tmpl, *, segment):
    """Overlap-save correlation of one complex spectrum against every
    template row: out[t, r] = sum_j fser[r - half + j] * conj(tmpl[t, j])
    with ``half = (width-1)//2`` — the matched-filter output centred on
    bin r, for all nbins r and all T templates.

    The spectrum is cut into ``segment``-length windows advancing by
    ``step = segment - (width - 1)`` bins; each window's circular FFT
    correlation is valid (wraparound-free) on its first ``step``
    outputs, which tile the full output exactly. ``segment`` is a
    static power of two (fdas/templates.py:auto_segment), so the FFTs
    stay in the sizes the fft machinery is fastest at and the compiled
    shape is independent of nbins' factorisation.

    Each template row's output depends only on that row: bitwise at a
    fixed batch shape, and to f32 rounding across the shapes a split
    of the bank gives (XLA's CPU FFT rounds per batch shape) — pinned
    by tests/test_fdas.py.
    """
    nbins = fser.shape[-1]
    ntmpl, width = tmpl.shape
    half = (width - 1) // 2
    step = segment - (width - 1)
    if step <= 0:
        raise ValueError(
            f"segment {segment} too short for template width {width}"
        )
    tpad = -(-ntmpl // _ROW_ALIGN) * _ROW_ALIGN
    if tpad != ntmpl:
        tmpl = jnp.pad(tmpl, ((0, tpad - ntmpl), (0, 0)))
    nseg = -(-nbins // step)
    total = nseg * step + width - 1
    fpad = jnp.pad(fser, (half, total - nbins - half))
    starts = jnp.arange(nseg) * step
    segs = fpad[starts[:, None] + jnp.arange(segment)[None, :]]
    tf = jnp.conj(jnp.fft.fft(tmpl, n=segment, axis=-1))  # (tpad, segment)
    sf = jnp.fft.fft(segs, axis=-1)  # (nseg, segment)
    y = jnp.fft.ifft(sf[None, :, :] * tf[:, None, :], axis=-1)
    y = y[..., :step].reshape(tpad, nseg * step)[:ntmpl, :nbins]
    return y.astype(jnp.complex64)


def fdas_trial_core(
    tim: jax.Array,  # (>=size,) u8/f32 dedispersed time series
    tmpl: jax.Array,  # (T, width) c64 template batch (unit energy)
    zapmask: jax.Array,  # (size//2+1,) bool birdie mask
    windows: jax.Array,  # (nharms+1, 2) i32 [start, limit) per level
    *,
    threshold: float,
    size: int,
    nsamps_valid: int,
    segment: int,
    nharms: int,
    max_peaks: int,
    pos5: int,
    pos25: int,
):
    """Pure FDAS body for one DM trial; vmap-compatible. Returns
    per-level (nharms+1, T, max_peaks) peak sets."""
    x = _pad_trial(tim, size=size, nsamps_valid=nsamps_valid)
    fser = whiten_fseries(x, pos5=pos5, pos25=pos25)
    fser = zap_birdies(fser, zapmask)
    # normalisation stats come from the ZERO-drift spectrum (identical
    # to the plain chain's), so every template row is scored against
    # the same noise floor and the z=0 row reproduces the plain search
    s0 = form_interpolated(fser)
    mean, _, std = spectrum_stats(s0)
    with jax.named_scope("FDAS-Correlate"):
        corr = correlate_bank(fser, tmpl, segment=segment)  # (T, nbins)
    s = form_interpolated(corr)
    s = normalise(s, mean, std)
    with jax.named_scope("Harmonic summing"):
        sums = harmonic_sums(s, nharms=nharms, scaled=True)
    levels = [s] + sums
    idxs, snrs, counts, ccounts = [], [], [], []
    nbins = size // 2 + 1
    with jax.named_scope("Peaks"):
        for lvl, spec in enumerate(levels):
            i_, s_, c_ = find_peaks_device(
                spec,
                jnp.float32(threshold),
                windows[lvl, 0],
                windows[lvl, 1],
                max_peaks=max_peaks,
            )
            i_, s_, cc_ = cluster_peaks_device(i_, s_, jnp.int32(nbins))
            idxs.append(i_)
            snrs.append(s_)
            counts.append(c_)
            ccounts.append(cc_)
    return (
        jnp.stack(idxs, axis=0),
        jnp.stack(snrs, axis=0),
        jnp.stack(counts, axis=0),
        jnp.stack(ccounts, axis=0),
    )


def fdas_block_core(
    tims: jax.Array,  # (D, >=size) dedispersed time-series block
    tmpl: jax.Array,  # (T, width) c64 template batch
    zapmask: jax.Array,
    windows: jax.Array,
    *,
    threshold: float,
    size: int,
    nsamps_valid: int,
    segment: int,
    nharms: int,
    max_peaks: int,
    pos5: int,
    pos25: int,
) -> FdasPeaks:
    """Block-batched FDAS: the (D, T) DM-x-template tile as one array
    program. The template batch is shared across the block (templates
    depend only on the bank geometry, not the DM trial)."""
    i_, s_, c_, cc_ = jax.vmap(
        lambda tim: fdas_trial_core(
            tim, tmpl, zapmask, windows,
            threshold=threshold, size=size, nsamps_valid=nsamps_valid,
            segment=segment, nharms=nharms, max_peaks=max_peaks,
            pos5=pos5, pos25=pos25,
        )
    )(tims)
    return FdasPeaks(idxs=i_, snrs=s_, counts=c_, ccounts=cc_)


@lru_cache(maxsize=None)
def make_fdas_search_fn(threshold: float):
    """Build the jitted FDAS block program with the S/N threshold
    bound statically. Cached so repeat runs with the same threshold
    reuse the compiled executable; the driver dispatches a fixed
    (dm_block, template_batch) tile so ONE compile covers the run."""

    @partial(
        jax.jit,
        static_argnames=(
            "size", "nsamps_valid", "segment", "nharms", "max_peaks",
            "pos5", "pos25",
        ),
    )
    def fdas_dm_block(tims, tmpl, zapmask, windows, *, size, nsamps_valid,
                      segment, nharms, max_peaks, pos5, pos25) -> FdasPeaks:
        return fdas_block_core(
            tims, tmpl, zapmask, windows,
            threshold=threshold, size=size, nsamps_valid=nsamps_valid,
            segment=segment, nharms=nharms, max_peaks=max_peaks,
            pos5=pos5, pos25=pos25,
        )

    return fdas_dm_block


# --- audit registry: representative build at toy shapes; the ShapeCtx
# hook rebuilds at a campaign fdas bucket's production geometry (the
# (dm_block, fdas_templates, fft_size, fdas_segment) tile derived by
# perf.warmup.shape_ctx_for_bucket from the SAME fdas/templates.py
# geometry formulas the driver uses), so AOT warmup compiles exactly
# the program pipeline/fdas.py will dispatch ---
from .registry import register_program, sds  # noqa: E402


def _fdas_width(ctx):
    """Template width implied by the ctx's zmax via the shared
    geometry formula — the bank builder, driver and this hook all call
    fdas/templates.py so the compiled shapes agree."""
    from ..fdas.templates import template_half_width

    return 2 * template_half_width(ctx.fdas_zmax) + 1


def _param_fdas(ctx):
    if ctx.fdas_templates <= 0 or ctx.fft_size <= 0:
        return None  # not an FDAS ctx
    width = _fdas_width(ctx)
    # the driver uploads trials[:, :min(size, out_nsamps)] — the traced
    # time axis is the VALID length, not the padded fft size
    tlen = min(ctx.out_nsamps or ctx.fft_size, ctx.fft_size)
    return (
        make_fdas_search_fn(float(ctx.min_snr)),
        (
            sds((ctx.dm_block, tlen), "uint8"),
            sds((ctx.fdas_templates, width), "complex64"),
            sds((ctx.fft_size // 2 + 1,), "bool"),
            sds((ctx.nharms + 1, 2), "int32"),
        ),
        {
            "size": ctx.fft_size,
            "nsamps_valid": tlen,
            "segment": ctx.fdas_segment,
            "nharms": ctx.nharms,
            "max_peaks": ctx.max_peaks,
            "pos5": ctx.pos5,
            "pos25": ctx.pos25,
        },
    )


register_program(
    "ops.fdas.fdas_correlate_search",
    lambda: (
        make_fdas_search_fn(6.0),
        (
            sds((2, 4096), "uint8"),
            sds((5, 65), "complex64"),
            sds((2049,), "bool"),
            sds((3, 2), "int32"),
        ),
        {
            "size": 4096, "nsamps_valid": 4096, "segment": 1024,
            "nharms": 2, "max_peaks": 32, "pos5": 2, "pos25": 10,
        },
    ),
    param=_param_fdas,
)
# segment is a STATIC knob (it sizes the overlap-save FFTs), so the
# registered form binds it via static_argnames — the contract engine
# traces exactly the executable the fused program inlines
_correlate_bank_jit = jax.jit(correlate_bank, static_argnames=("segment",))

register_program(
    "ops.fdas.correlate_bank",
    lambda: (
        _correlate_bank_jit,
        (sds((2049,), "complex64"), sds((5, 65), "complex64")),
        {"segment": 1024},
    ),
    param=lambda ctx: None if ctx.fdas_templates <= 0 else (
        _correlate_bank_jit,
        (
            sds((ctx.fft_size // 2 + 1,), "complex64"),
            sds((ctx.fdas_templates, _fdas_width(ctx)), "complex64"),
        ),
        {"segment": ctx.fdas_segment},
    ),
)
