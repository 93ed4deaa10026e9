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

from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Literal, NamedTuple

import jax
import jax.numpy as jnp

from ..ops.fft import _MIN_N
from ..ops.harmonics import harmonic_sums
from ..ops.pallas.dftspec import dftspec_supported
from ..ops.pallas.peaks import PEAKS_BLOCK
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


@dataclass(frozen=True)
class SearchRoute:
    """Which search program runs. ``kernels``: the TPU kernel chain,
    the fused spectrum chain (ops/pallas/specchain.py) and the
    harmonic-sum + peaks kernel (ops/pallas/harmpeaks.py); without it
    the jnp twins run. ``spectrum``: how each resampled series becomes
    a normalised spectrum — "dftspec" (one Pallas DFT + interbin
    kernel, ops/pallas/dftspec.py), "interbin" (the four-step einsum
    DFT + the interbin kernel, ops/pallas/interbin.py) or "fft" (jnp
    rfft + interbin). Decided by :func:`search_route` alone."""

    kernels: bool = False
    spectrum: Literal["dftspec", "interbin", "fft"] = "fft"


CPU_ROUTE = SearchRoute()


def spectrum_pad(nbins: int) -> int:
    """Bins of a spectrum padded to the harmonic-peaks kernel's block."""
    return -(-nbins // PEAKS_BLOCK) * PEAKS_BLOCK


def interbin_fits(size: int) -> bool:
    """The interbin kernel's shape precondition: a power-of-two series
    of at least 2^14 points whose half fills whole kernel blocks."""
    return (
        size >= _MIN_N
        and not (size & (size - 1))
        and (size // 2) % PEAKS_BLOCK == 0
    )


def search_route(backend: str, size: int, packed: bool) -> SearchRoute:
    """The search program for a platform and series length. Off TPU the
    jnp twins run. On TPU the kernel chain runs over the DFT kernel
    where the shape admits it and the resample emits even/odd sample
    planes (``packed``: the select path), else over the einsum DFT and
    the interbin kernel, else (below 2^14 points) over the jnp rfft."""
    if backend != "tpu":
        return CPU_ROUTE
    spectrum = "fft"
    if interbin_fits(size):
        dft = packed and dftspec_supported(size, spectrum_pad(size // 2 + 1))
        spectrum = "dftspec" if dft else "interbin"
    return SearchRoute(kernels=True, spectrum=spectrum)


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
    tims, zapmask, *, size, nsamps_valid, pos5, pos25, interpret=False
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
    re_d, im_d, s0 = interp_deredden_zap_pallas(
        re, im, med, zapmask, interpret=interpret
    )
    mean, _, std = spectrum_stats(s0)
    xd = jnp.fft.irfft(jax.lax.complex(re_d, im_d), n=size)
    return xd, mean, std


def _spectra_and_peaks(
    xr, mean, std, windows, *, threshold, nharms, max_peaks, stack_axis,
    cluster=True, route=CPU_ROUTE, interpret=False,
):
    """Post-resample stage: batched rfft, interbin, normalise, harmonic
    sums, per-level peak compaction (pipeline_multi.cu:216-234), and —
    with ``cluster`` — the min-gap peak clustering the reference runs
    on the host (peakfinder.hpp:27-56), kept on device so only cluster
    peaks ever cross the host link. On the kernel route the harmonic
    sums, compaction and clustering run as one Pallas kernel
    (ops/pallas/harmpeaks.py): same outputs, but idxs/snrs hold CLUSTER
    peaks sized ``max_peaks`` while raw crossings are only counted —
    overflow then means ccounts > max_peaks, not counts. ``xr`` is
    (..., A, size), or its even/odd sample planes for the kernel
    spectra; mean/std broadcast against (..., A)."""
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
    kernels = route.kernels and cluster
    spectrum = route.spectrum if kernels else "fft"
    with jax.named_scope("Acceleration-Loop"):
        if spectrum == "fft":
            s = form_interpolated(jnp.fft.rfft(xr, axis=-1))
            s = normalise(s, mean, std)
        else:
            # the kernels emit the spectrum already padded to the
            # harmonic-peaks kernel's block alignment
            batch = xr[0].shape[:-2] if shaped else (
                xr[0].shape[:-1] if packed else xr.shape[:-1]
            )
            npad = spectrum_pad(nbins)
            if spectrum == "dftspec":
                # one Pallas kernel does DFT + untwist + interbin +
                # normalise per row stripe in VMEM (ops/pallas/
                # dftspec.py): no einsum layout copies, no Z round
                # trip. 3-pass HIGH-class accuracy, validated end to
                # end by the golden-recall gate. The select writes the
                # (.., n1, n2) planes in the kernel's tile layout
                from ..ops.pallas.dftspec import dft_untwist_interbin

                n1, n2 = xr[0].shape[-2:]
                pe = xr[0].reshape(-1, n1, n2)
                po = xr[1].reshape(-1, n1, n2)
                s = dft_untwist_interbin(
                    pe, po,
                    jnp.broadcast_to(mean, batch).reshape(-1),
                    jnp.broadcast_to(std, batch).reshape(-1),
                    npad=npad, interpret=interpret,
                ).reshape(*batch, npad)
            else:
                # matmul four-step packed DFT, then ONE Pallas pass
                # does untwist + interbin + normalise
                # (ops/pallas/interbin.py)
                from ..ops.fft import packed_dft_z, packed_dft_z_parts
                from ..ops.pallas.interbin import untwist_interbin_normalise

                zr, zi = (
                    packed_dft_z_parts(*xr) if packed else packed_dft_z(xr)
                )
                s = untwist_interbin_normalise(
                    zr, zi,
                    jnp.broadcast_to(mean, batch).reshape(-1),
                    jnp.broadcast_to(std, batch).reshape(-1),
                    npad=npad, block=PEAKS_BLOCK, interpret=interpret,
                ).reshape(*batch, npad)
    if kernels:
        # harmonic summing FUSED into the peaks walk: one Pallas
        # dispatch gathers, accumulates, scales (rsqrt(2^h) per level),
        # thresholds and clusters every level in VMEM
        # (ops/pallas/harmpeaks.py). Bitwise-equal outputs (probe-gated).
        lvl_scales = (1.0,) + tuple(
            2.0 ** (-h / 2.0) for h in range(1, nharms + 1)
        )
        with jax.named_scope("Harmonic summing"):
            from ..ops.pallas.harmpeaks import find_harmonic_cluster_peaks

            npad = spectrum_pad(nbins)
            if s.shape[-1] != npad:
                s = jnp.pad(
                    s, [(0, 0)] * (s.ndim - 1) + [(0, npad - s.shape[-1])]
                )
            i_, s_, c_, cc_ = find_harmonic_cluster_peaks(
                s, windows, nharms=nharms, threshold=threshold,
                max_peaks=max_peaks, scales=lvl_scales, nbins=nbins,
                interpret=interpret,
            )
        nb = s.ndim - 1  # batch rank
        return AccelSearchPeaks(
            idxs=jnp.moveaxis(i_, nb, stack_axis),
            snrs=jnp.moveaxis(s_, nb, stack_axis),
            counts=jnp.moveaxis(c_, nb, stack_axis),
            ccounts=jnp.moveaxis(cc_, nb, stack_axis),
        )

    with jax.named_scope("Harmonic summing"):
        sums = harmonic_sums(s, nharms=nharms, scaled=True)
    levels = [s] + sums

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
    route: SearchRoute = CPU_ROUTE,
) -> AccelSearchPeaks:
    """Block-batched search: all per-DM preprocessing vmapped, then the
    (D, A) accel grid processed as single batched array programs. With
    ``pallas_block`` > 0 the resampling gather runs as the Pallas
    windowed-select kernel (ops/pallas/resample.py); with
    ``select_smax`` > 0 as the gather-free jnp select
    (ops/resample.py:resample_select); otherwise the jnp gather twin.
    Results are bitwise identical in all three modes. ``route`` (see
    :func:`search_route`) picks the spectrum and peak programs; on the
    kernel route the once-per-trial deredden -> zap -> interbin tail
    runs as the fused Pallas pass. ``pallas_interpret`` runs every
    Pallas kernel in interpret mode (tests).
    """
    # the kernel spectra feed only the harmonic-peaks kernel, which
    # emits cluster peaks
    spectrum = route.spectrum if route.kernels and cluster else "fft"
    # named scopes mirror the roofline stage classification
    # (tools/scope_trace STAGE_RULES), so profiler traces attribute
    # this one jitted program's device time per stage
    with jax.named_scope("Spectrum-Chain"):
        if route.kernels:
            xd, mean, std = _preprocess_block_fused(
                tims, zapmask, size=size, nsamps_valid=nsamps_valid,
                pos5=pos5, pos25=pos25, interpret=pallas_interpret,
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
        elif select_smax > 0 and spectrum == "dftspec":
            # the DFT kernel wants even/odd planes PRE-SHAPED (.., n1,
            # n2): the select writes its tile layout directly, with no
            # stride-2 deinterleave or relayout pass (bitwise-equal
            # elements, ops/resample.py)
            from ..ops.pallas.dftspec import plane_factors
            from ..ops.resample import resample_select_packed_planes

            n1, n2 = plane_factors(size // 2)
            xr = resample_select_packed_planes(
                xd, afs, smax=select_smax, n1=n1, n2=n2
            )
        elif select_smax > 0 and spectrum == "interbin":
            # the packed einsum DFT wants even/odd planes
            from ..ops.resample import resample_select_packed

            xr = resample_select_packed(xd, afs, smax=select_smax)
        elif select_smax > 0:
            from ..ops.resample import resample_select

            xr = resample_select(xd, afs, smax=select_smax)
        else:
            xr = jax.vmap(resample_accel)(xd, afs)  # (D, A, size)

    # stack levels at axis 1 -> (D, nharms+1, A, ...) to match
    # vmap(search_trial_core)'s layout
    return _spectra_and_peaks(
        xr, mean[:, None], std[:, None], windows,
        threshold=threshold, nharms=nharms, max_peaks=max_peaks,
        stack_axis=1, cluster=cluster, route=route,
        interpret=pallas_interpret,
    )


@lru_cache(maxsize=None)
def make_batched_search_fn(
    threshold: float, pallas_block: int = 0, select_smax: int = 0,
    route: SearchRoute = CPU_ROUTE,
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
            cluster=cluster, route=route,
        )

    return search_dm_block
