"""The per-DM-trial acceleration-search device program.

This is the TPU replacement for the reference's hot loop
(Worker::start, src/pipeline_multi.cu:144-243): where the CUDA code
runs one FFT/spectrum/harmonic/peak pass per acceleration trial, here
the WHOLE acceleration batch for a DM trial is one jitted array
program — resampling is a (A, N) gather, the FFT is one batched rfft,
and peak extraction is a masked static-size compaction per harmonic
level. Python never touches per-trial spectra.

Stages (reference line refs in parentheses):
  pad/truncate (pipeline_multi.cu:112-114,160-163) -> rfft (174) ->
  |.| (178) -> running median (182) -> deredden (186) -> zap (188-192)
  -> interbin + stats (196-200) -> irfft (204) -> per-accel: resample
  (212), rfft (216), interbin (220), normalise (224), harmonic sums
  (228), peak extraction (233-234).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops.harmonics import harmonic_sums
from ..ops.peaks import cluster_peaks_device, find_peaks_device
from ..ops.rednoise import whiten_fseries
from ..ops.resample import resample_accel
from ..ops.spectrum import form_interpolated, normalise, spectrum_stats
from ..ops.zap import zap_birdies


class AccelSearchPeaks(NamedTuple):
    """Static-size peak sets for one DM trial.

    idxs/snrs: (nharms+1, A, max_peaks) — level 0 is the fundamental
    spectrum, level h the 2^h-harmonic sum. counts: (nharms+1, A) raw
    threshold crossings (the overflow-escalation signal). With
    on-device clustering (``cluster=True``, the default) idxs/snrs hold
    the min-gap CLUSTER peaks (identify_unique_peaks semantics) and
    ccounts their per-cell count; without it ccounts == counts and
    idxs/snrs are the raw crossings.
    """

    idxs: jax.Array
    snrs: jax.Array
    counts: jax.Array
    ccounts: jax.Array


def _pad_trial(tim, *, size, nsamps_valid):
    """Pad/truncate one trial to ``size`` with the reference's
    mean-padded tail (pipeline_multi.cu:160-163)."""
    x = tim[:size].astype(jnp.float32)
    if nsamps_valid < size:
        # the input trial may be shorter than size, so pad to shape first
        x = jnp.pad(x, (0, size - x.shape[0]))
        mean_head = jnp.mean(x[:nsamps_valid])
        idx = jnp.arange(size)
        x = jnp.where(idx < nsamps_valid, x, mean_head)
    return x


def _preprocess_trial(tim, zapmask, *, size, nsamps_valid, pos5, pos25):
    """Once-per-DM-trial stage: pad, whiten, zap, stats, back to time
    domain (pipeline_multi.cu:160-204). Returns (xd, mean, std)."""
    x = _pad_trial(tim, size=size, nsamps_valid=nsamps_valid)
    fser = whiten_fseries(x, pos5=pos5, pos25=pos25)
    fser = zap_birdies(fser, zapmask)
    s0 = form_interpolated(fser)
    mean, _, std = spectrum_stats(s0)
    xd = jnp.fft.irfft(fser, n=size)
    return xd, mean, std


def _pre_spectrum_parts(tim, *, size, nsamps_valid, pos5, pos25):
    """The fused-chain front half for one trial: pad, rfft, running
    median — returning the raw spectrum PARTS the fused
    deredden+zap+interbin pass consumes (vmapped over the block)."""
    from ..ops.rednoise import running_median
    from ..ops.spectrum import form_power

    x = _pad_trial(tim, size=size, nsamps_valid=nsamps_valid)
    fser = jnp.fft.rfft(x)
    med = running_median(form_power(fser), pos5=pos5, pos25=pos25)
    return (
        jnp.real(fser).astype(jnp.float32),
        jnp.imag(fser).astype(jnp.float32),
        med,
    )


def _preprocess_block_fused(
    tims, zapmask, *, size, nsamps_valid, pos5, pos25
):
    """Block-batched once-per-DM-trial stage with the spectrum-chain
    tail (deredden -> zap -> interbin) FUSED into one Pallas pass over
    the whole (D, nbins) batch (ops/pallas/specchain.py; callers gate
    on probe_pallas_specchain). Returns (xd, mean, std) like the
    vmapped :func:`_preprocess_trial`."""
    from ..ops.pallas.specchain import interp_deredden_zap_pallas

    re, im, med = jax.vmap(
        lambda tim: _pre_spectrum_parts(
            tim, size=size, nsamps_valid=nsamps_valid, pos5=pos5,
            pos25=pos25,
        )
    )(tims)
    re_d, im_d, s0 = interp_deredden_zap_pallas(re, im, med, zapmask)
    mean, _, std = spectrum_stats(s0)
    xd = jnp.fft.irfft(jax.lax.complex(re_d, im_d), n=size)
    return xd, mean, std


def _spectra_and_peaks(
    xr, mean, std, windows, *, threshold, nharms, max_peaks, stack_axis,
    cluster=True, pallas_peaks=False, fused_interbin=False,
    mega_harm=False, fused_dft=False,
):
    """Post-resample stage: batched rfft, interbin, normalise, harmonic
    sums, per-level peak compaction (pipeline_multi.cu:216-234), and —
    with ``cluster`` — the min-gap peak clustering the reference runs
    on the host (peakfinder.hpp:27-56), kept on device so only cluster
    peaks ever cross the host link. With ``pallas_peaks`` the
    compaction + clustering run as the fused streaming kernel
    (ops/pallas/peaks.py): same outputs, but idxs/snrs hold CLUSTER
    peaks sized ``max_peaks`` while raw crossings are only counted —
    overflow then means ccounts > max_peaks, not counts. ``xr`` is
    (..., A, size); mean/std broadcast against (..., A)."""
    # named scopes mirror the reference's NVTX ranges inside the jitted
    # program (pipeline_multi.cu:207, harmonicfolder.hpp:28): ops carry
    # the scope in their metadata, so profiler traces group them
    packed = isinstance(xr, tuple)  # pre-deinterleaved (even, odd) planes
    # 4-D packed planes are pre-shaped (.., n1, n2) for the fused DFT
    # kernel (resample_select_packed_planes): flat sample count is the
    # product of the two plane dims
    shaped = packed and xr[0].ndim == 4
    if shaped:
        size = 2 * xr[0].shape[-2] * xr[0].shape[-1]
    else:
        size = 2 * xr[0].shape[-1] if packed else xr.shape[-1]
    nbins = size // 2 + 1
    kernel_scales = pallas_peaks and cluster
    # per-level rsqrt(2^h) factors, applied in VMEM by the kernel paths
    # and pre-applied by harmonic_sums(scaled=True) on the jnp path
    lvl_scales = (1.0,) + tuple(
        2.0 ** (-h / 2.0) for h in range(1, nharms + 1)
    )
    with jax.named_scope("Acceleration-Loop"):
        from ..ops.fft import _use_matmul, rfft_pow2_matmul_parts
        from ..ops.spectrum import form_interpolated_parts

        if fused_interbin and kernel_scales:
            # matmul four-step packed DFT, then ONE Pallas pass does
            # untwist + interbin + normalise and emits the spectrum
            # already padded to the peaks kernel's block alignment
            # (ops/pallas/interbin.py) — callers gate on the
            # probe_pallas_interbin oracle
            from ..ops.fft import packed_dft_z, packed_dft_z_parts
            from ..ops.pallas.interbin import untwist_interbin_normalise
            from ..ops.pallas.peaks import PEAKS_BLOCK

            batch = (
                xr[0].shape[:-2] if shaped
                else xr[0].shape[:-1] if packed else xr.shape[:-1]
            )
            npad = -(-nbins // PEAKS_BLOCK) * PEAKS_BLOCK
            if fused_dft and packed:
                # one Pallas kernel does DFT + untwist + interbin +
                # normalise per row stripe in VMEM (ops/pallas/
                # dftspec.py): kills the einsum layout copies and the
                # Z round trip. 3-pass HIGH-class accuracy, validated
                # end to end by the golden-recall gate (probe-gated;
                # PEASOUP_FUSED_DFT=0 restores this einsum chain).
                # Producers send (.., n1, n2) pre-shaped planes so the
                # select writes the kernel's tile layout directly
                # (flat planes would relayout-copy here)
                from ..ops.pallas.dftspec import dft_untwist_interbin

                if shaped:
                    n1, n2 = xr[0].shape[-2:]
                    pe = xr[0].reshape(-1, n1, n2)
                    po = xr[1].reshape(-1, n1, n2)
                else:
                    half = xr[0].shape[-1]
                    pe = xr[0].reshape(-1, half)
                    po = xr[1].reshape(-1, half)
                s = dft_untwist_interbin(
                    pe, po,
                    jnp.broadcast_to(mean, batch).reshape(-1),
                    jnp.broadcast_to(std, batch).reshape(-1),
                    npad=npad,
                ).reshape(*batch, npad)
            else:
                zr, zi = (
                    packed_dft_z_parts(*xr) if packed else packed_dft_z(xr)
                )
                s = untwist_interbin_normalise(
                    zr, zi,
                    jnp.broadcast_to(mean, batch).reshape(-1),
                    jnp.broadcast_to(std, batch).reshape(-1),
                    npad=npad, block=PEAKS_BLOCK,
                ).reshape(*batch, npad)
        elif _use_matmul(xr.shape[-1]):
            # matmul four-step rfft as lazy (re, im) parts: the untwist
            # fuses into the interbin pass (no complex materialisation)
            s = form_interpolated_parts(*rfft_pow2_matmul_parts(xr))
            s = normalise(s, mean, std)
        else:
            s = form_interpolated(jnp.fft.rfft(xr, axis=-1))
            s = normalise(s, mean, std)
    if mega_harm and pallas_peaks and cluster:
        # harmonic summing FUSED into the peaks walk: one Pallas
        # dispatch gathers, accumulates, scales, thresholds and
        # clusters every level in VMEM (ops/pallas/harmpeaks.py) —
        # no conv val-chain HBM round trips, no level arrays, no
        # layout copies. Bitwise-equal outputs (probe-gated).
        with jax.named_scope("Harmonic summing"):
            from ..ops.pallas.harmpeaks import find_harmonic_cluster_peaks
            from ..ops.pallas.peaks import PEAKS_BLOCK

            npad = -(-nbins // PEAKS_BLOCK) * PEAKS_BLOCK
            if s.shape[-1] != npad:
                s = jnp.pad(
                    s, [(0, 0)] * (s.ndim - 1) + [(0, npad - s.shape[-1])]
                )
            i_, s_, c_, cc_ = find_harmonic_cluster_peaks(
                s, windows, nharms=nharms, threshold=threshold,
                max_peaks=max_peaks, scales=lvl_scales, nbins=nbins,
            )
        nb = s.ndim - 1  # batch rank
        return AccelSearchPeaks(
            idxs=jnp.moveaxis(i_, nb, stack_axis),
            snrs=jnp.moveaxis(s_, nb, stack_axis),
            counts=jnp.moveaxis(c_, nb, stack_axis),
            ccounts=jnp.moveaxis(cc_, nb, stack_axis),
        )

    # the fused kernel applies the per-level rsqrt(2^h) factor in VMEM
    # (one fewer full HBM pass per level); the jnp path scales here.
    # For the kernel path the levels also come back pre-padded to the
    # kernel's block size (block_align) so no per-level pad pass is
    # spent — the pad region is garbage the kernel's windows mask.
    with jax.named_scope("Harmonic summing"):
        if kernel_scales:
            from ..ops.pallas.peaks import PEAKS_BLOCK

            sums = harmonic_sums(
                s, nharms=nharms, scaled=False, block_align=PEAKS_BLOCK
            )
            npad = sums[0].shape[-1]
            if s.shape[-1] != npad:
                s = jnp.pad(
                    s, [(0, 0)] * (s.ndim - 1) + [(0, npad - nbins)]
                )
        else:
            sums = harmonic_sums(s, nharms=nharms, scaled=True)
    levels = [s] + sums

    if pallas_peaks and cluster:
        # ONE kernel dispatch walks every level's threshold+cluster
        # machine together (ops/pallas/peaks.py:find_cluster_peaks_multi)
        from ..ops.pallas.peaks import find_cluster_peaks_multi

        with jax.named_scope("Peaks"):
            i_, s_, c_, cc_ = find_cluster_peaks_multi(
                levels, windows, threshold=threshold, max_peaks=max_peaks,
                scales=lvl_scales, nbins=nbins,
            )
        # kernel emits (..., nlev, ...); the NamedTuple wants the level
        # axis at stack_axis
        nb = len(levels[0].shape) - 1  # batch rank
        return AccelSearchPeaks(
            idxs=jnp.moveaxis(i_, nb, stack_axis),
            snrs=jnp.moveaxis(s_, nb, stack_axis),
            counts=jnp.moveaxis(c_, nb, stack_axis),
            ccounts=jnp.moveaxis(cc_, nb, stack_axis),
        )

    idxs, snrs, counts, ccounts = [], [], [], []
    with jax.named_scope("Peaks"):
        for lvl, spec in enumerate(levels):
            i_, s_, c_ = find_peaks_device(
                spec,
                jnp.float32(threshold),
                windows[lvl, 0],
                windows[lvl, 1],
                max_peaks=max_peaks,
            )
            if cluster:
                i_, s_, cc_ = cluster_peaks_device(
                    i_, s_, jnp.int32(nbins)
                )
            else:
                cc_ = c_
            idxs.append(i_)
            snrs.append(s_)
            counts.append(c_)
            ccounts.append(cc_)
    return AccelSearchPeaks(
        idxs=jnp.stack(idxs, axis=stack_axis),
        snrs=jnp.stack(snrs, axis=stack_axis),
        counts=jnp.stack(counts, axis=stack_axis),
        ccounts=jnp.stack(ccounts, axis=stack_axis),
    )


def search_trial_core(
    tim: jax.Array,  # (>=size,) u8/f32 dedispersed time series
    afs: jax.Array,  # (A,) f32 acceleration factors a*tsamp/2c (padded)
    zapmask: jax.Array,  # (size//2+1,) bool birdie mask
    windows: jax.Array,  # (nharms+1, 2) i32 [start_idx, limit) per level
    *,
    threshold: float,
    size: int,
    nsamps_valid: int,
    nharms: int,
    max_peaks: int,
    pos5: int,
    pos25: int,
    cluster: bool = True,
) -> AccelSearchPeaks:
    """Pure search body for one DM trial; vmap/shard_map-compatible."""
    xd, mean, std = _preprocess_trial(
        tim, zapmask, size=size, nsamps_valid=nsamps_valid,
        pos5=pos5, pos25=pos25,
    )
    xr = resample_accel(xd, afs)  # (A, size)
    return _spectra_and_peaks(
        xr, mean[None], std[None], windows,
        threshold=threshold, nharms=nharms, max_peaks=max_peaks,
        stack_axis=0, cluster=cluster,
    )


@lru_cache(maxsize=None)
def make_search_fn(threshold: float):
    """Build the jitted per-DM-trial program with the S/N threshold
    bound statically (it never changes within a run). Cached so repeat
    runs with the same threshold reuse the compiled executable."""

    @partial(
        jax.jit,
        static_argnames=("size", "nsamps_valid", "nharms", "max_peaks", "pos5",
                         "pos25", "cluster"),
    )
    def search_dm_trial(tim, afs, zapmask, windows, *, size, nsamps_valid,
                        nharms, max_peaks, pos5, pos25,
                        cluster=True) -> AccelSearchPeaks:
        return search_trial_core(
            tim, afs, zapmask, windows,
            threshold=threshold, size=size, nsamps_valid=nsamps_valid,
            nharms=nharms, max_peaks=max_peaks, pos5=pos5, pos25=pos25,
            cluster=cluster,
        )

    return search_dm_trial


def search_block_core(
    tims: jax.Array,  # (D, >=size) u8/f32 dedispersed time series block
    afs: jax.Array,  # (D, A) f32 acceleration factors (padded)
    zapmask: jax.Array,
    windows: jax.Array,
    *,
    threshold: float,
    size: int,
    nsamps_valid: int,
    nharms: int,
    max_peaks: int,
    pos5: int,
    pos25: int,
    pallas_block: int = 0,
    pallas_interpret: bool = False,
    select_smax: int = 0,
    cluster: bool = True,
    pallas_peaks: bool = False,
    fused_interbin: bool = False,
    mega_harm: bool = False,
    fused_dft: bool = False,
    fused_spec: bool = False,
) -> AccelSearchPeaks:
    """Block-batched search: all per-DM preprocessing vmapped, then the
    (D, A) accel grid processed as single batched array programs. With
    ``pallas_block`` > 0 the resampling gather runs as the Pallas
    windowed-select kernel (ops/pallas/resample.py); with
    ``select_smax`` > 0 as the gather-free jnp select
    (ops/resample.py:resample_select); otherwise the jnp gather twin.
    Results are bitwise identical in all three modes. ``fused_spec``
    routes the once-per-trial deredden -> zap -> interbin tail through
    the fused Pallas pass (probe-gated by the caller).
    """
    # named scopes mirror the roofline stage classification
    # (tools/scope_trace STAGE_RULES), so profiler traces attribute
    # this one jitted program's device time per stage
    with jax.named_scope("Spectrum-Chain"):
        if fused_spec:
            xd, mean, std = _preprocess_block_fused(
                tims, zapmask, size=size, nsamps_valid=nsamps_valid,
                pos5=pos5, pos25=pos25,
            )
        else:
            xd, mean, std = jax.vmap(
                lambda tim: _preprocess_trial(
                    tim, zapmask, size=size, nsamps_valid=nsamps_valid,
                    pos5=pos5, pos25=pos25,
                )
            )(tims)  # (D, size), (D,), (D,)

    with jax.named_scope("Resample"):
        if pallas_block > 0:
            from ..ops.pallas.resample import resample_block_pallas

            xr = resample_block_pallas(
                xd, afs, block=pallas_block, interpret=pallas_interpret
            )
        elif select_smax > 0:
            if fused_interbin and cluster and pallas_peaks:
                # the packed-DFT consumer wants even/odd planes:
                # selecting straight into them skips the stride-2
                # deinterleave relayout (bitwise-equal elements,
                # ops/resample.py). The fused-DFT kernel additionally
                # wants them PRE-SHAPED (.., n1, n2) so the select
                # writes its tile layout with no relayout pass
                # (resample_select_packed_planes)
                if fused_dft:
                    from ..ops.pallas.dftspec import plane_factors
                    from ..ops.resample import (
                        resample_select_packed_planes,
                    )

                    n1, n2 = plane_factors(size // 2)
                    xr = resample_select_packed_planes(
                        xd, afs, smax=select_smax, n1=n1, n2=n2
                    )
                else:
                    from ..ops.resample import resample_select_packed

                    xr = resample_select_packed(xd, afs, smax=select_smax)
            else:
                from ..ops.resample import resample_select

                xr = resample_select(xd, afs, smax=select_smax)
        else:
            xr = jax.vmap(resample_accel)(xd, afs)  # (D, A, size)

    # stack levels at axis 1 -> (D, nharms+1, A, ...) to match
    # vmap(search_trial_core)'s layout
    return _spectra_and_peaks(
        xr, mean[:, None], std[:, None], windows,
        threshold=threshold, nharms=nharms, max_peaks=max_peaks,
        stack_axis=1, cluster=cluster, pallas_peaks=pallas_peaks,
        fused_interbin=fused_interbin, mega_harm=mega_harm,
        fused_dft=fused_dft,
    )


@lru_cache(maxsize=None)
def make_batched_search_fn(
    threshold: float, pallas_block: int = 0, select_smax: int = 0,
    pallas_peaks: bool = False, fused_interbin: bool = False,
    mega_harm: bool = False, fused_dft: bool = False,
    fused_spec: bool = False,
):
    """Jitted (D, ...) -> (D, ...) search over a block of DM trials.

    A fixed (dm_block, accel_bucket) tile shape is the unit of device
    work (SURVEY.md §7): one compile covers the whole run, and the
    batching amortises dispatch — the reference instead launches ~10
    kernels per (DM, accel) pair (src/pipeline_multi.cu:209-239).
    """

    @partial(
        jax.jit,
        static_argnames=("size", "nsamps_valid", "nharms", "max_peaks", "pos5",
                         "pos25", "cluster"),
    )
    def search_dm_block(tims, afs, zapmask, windows, *, size, nsamps_valid,
                        nharms, max_peaks, pos5, pos25,
                        cluster=True) -> AccelSearchPeaks:
        return search_block_core(
            tims, afs, zapmask, windows,
            threshold=threshold, size=size, nsamps_valid=nsamps_valid,
            nharms=nharms, max_peaks=max_peaks, pos5=pos5, pos25=pos25,
            pallas_block=pallas_block, select_smax=select_smax,
            cluster=cluster, pallas_peaks=pallas_peaks,
            fused_interbin=fused_interbin, mega_harm=mega_harm,
            fused_dft=fused_dft, fused_spec=fused_spec,
        )

    return search_dm_block
