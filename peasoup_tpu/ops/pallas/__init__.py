"""Pallas TPU kernels for the hot ops.

Each kernel has a pure-jnp twin in ``peasoup_tpu.ops``: the oracle in
tests (interpret mode on CPU) and the path on non-TPU backends or when
a kernel's shape preconditions don't hold.

On a TPU backend the ``probe_pallas_*`` gates compile and run their
kernel at the caller's shape and check it against the twin. A kernel
that fails there raises :class:`KernelUnavailable`: the TPU route never
drops quietly to a slower twin.
"""

from __future__ import annotations

from contextlib import contextmanager
from functools import lru_cache

import jax


class KernelUnavailable(RuntimeError):
    """A Pallas kernel the TPU route selected failed to compile, run,
    or match its jnp oracle."""


def backend_supports_pallas() -> bool:
    """Compiled Mosaic kernels need a real TPU backend; everywhere else
    the kernels still run via the interpreter (tests) or the twins."""
    try:
        return jax.default_backend() == "tpu"
    except RuntimeError:
        return False


@contextmanager
def _on_chip(kernel: str, **shape):
    """Wrap one probe's compile + run + oracle check: any failure is
    re-raised naming the kernel, its shape and the compiler's message."""
    try:
        yield
    except Exception as exc:
        where = ", ".join(f"{k}={v}" for k, v in shape.items())
        raise KernelUnavailable(
            f"Pallas kernel {kernel!r} failed on this TPU"
            f"{f' at {where}' if where else ''}: "
            f"{type(exc).__name__}: {exc}"
        ) from exc


def _oracle(ok: bool) -> bool:
    if not ok:
        raise AssertionError("kernel output differs from its jnp oracle")
    return True


@lru_cache(maxsize=None)
def probe_pallas_resample(n: int, block: int) -> bool:
    """REAL compile+run probe of the resample kernel at the shape the
    caller is about to use (cached per (n, block)).

    The kernels are interpret-tested everywhere, but only the chip's
    compiler shows what Mosaic accepts, so the kernel is compiled and
    run with the production n and block (grid trimmed to one DM x one accel trial —
    the VMEM window, DMA shapes, and roll lowering are what vary with
    shape, and those are set by (n, block))."""
    if not backend_supports_pallas() or block <= 0:
        return False
    with _on_chip("resample", n=n, block=block):
        import numpy as np
        import jax
        import jax.numpy as jnp

        from .resample import resample_block_pallas
        from ..resample import resample_accel

        # af near the choose_block precondition limit: the shift walks
        # through every select arm, so a wrong pltpu.roll lowering (off
        # by a lane, wrong direction) cannot return oracle-equal data
        af = 1.9 / (float(n) * block)
        x = jnp.asarray(np.arange(n, dtype=np.float32).reshape(1, n))
        afs = jnp.asarray(np.asarray([[af, -af]], dtype=np.float32))
        out = np.asarray(resample_block_pallas(x, afs, block=block))
        # the kernel's index math is the same f32 ops as the jnp twin:
        # anything but bitwise equality means a broken lowering
        ref = np.asarray(resample_accel(x[0], afs[0]))
        return _oracle(
            out.shape == (1, 2, n) and np.array_equal(out[0], ref)
        )


@lru_cache(maxsize=None)
def probe_pallas_interbin(size: int, block: int) -> bool:
    """REAL compile+run probe of the fused untwist+interbin+normalise
    kernel (ops/pallas/interbin.py) at a small pow2 shape, gated on
    BITWISE equality with the jnp twin chain (rfft_pow2_matmul_parts ->
    form_interpolated_parts -> normalise): the kernel replays exactly
    the same f32 formulas, so any difference means a broken lowering
    (roll off by a lane, bad carry, wrong clamp). The features that
    vary by toolchain (static pltpu.roll, clamped block index maps,
    VMEM carry scratch) are shape-independent, so a small probe gates
    every production shape — at the PRODUCTION block width (Mosaic
    failures can be block-geometry-specific), with the probe's m scaled
    up to fit."""
    if not backend_supports_pallas():
        return False
    with _on_chip("interbin", size=size, block=block):
        import numpy as np
        import jax.numpy as jnp

        from .interbin import untwist_interbin_normalise
        from ..fft import rfft_pow2_matmul_parts
        from ..spectrum import form_interpolated_parts, normalise

        blk = block
        m = 8192 if 8192 % blk == 0 else 2 * blk
        n = 2 * m
        npad = m + blk
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(9, n)).astype(np.float32))
        mean = jnp.asarray(rng.normal(size=9).astype(np.float32))
        std = jnp.asarray((1.0 + rng.random(9)).astype(np.float32))
        from ..fft import packed_dft_z

        zr, zi = packed_dft_z(x)
        got = np.asarray(
            untwist_interbin_normalise(zr, zi, mean, std, npad=npad, block=blk)
        )
        ref = np.asarray(
            normalise(
                form_interpolated_parts(*rfft_pow2_matmul_parts(x)),
                mean, std,
            )
        )
        ok = (
            got.shape == (9, npad)
            and np.array_equal(got[:, : m + 1], ref)
            and not got[:, m + 1 :].any()
        )
        return _oracle(ok)


@lru_cache(maxsize=None)
def probe_pallas_harmpeaks(nbins: int, nharms: int, max_peaks: int) -> bool:
    """REAL compile+run probe of the harmonic+peaks mega-kernel
    (ops/pallas/harmpeaks.py) at the production bin count, oracle-
    checked BITWISE against harmonic_sums(method="take") + the jnp
    find_peaks_device/cluster_peaks_device pair: the kernel's one-hot
    MXU gathers and in-VMEM accumulation replay exactly the same f32
    chain, so any difference means a broken lowering (bad stream index
    map, inexact dot, mis-sliced window)."""
    if not backend_supports_pallas():
        return False
    with _on_chip("harmpeaks", nbins=nbins, nharms=nharms, max_peaks=max_peaks):
        import numpy as np
        import jax.numpy as jnp

        from .harmpeaks import find_harmonic_cluster_peaks
        from .peaks import PEAKS_BLOCK
        from ..harmonics import harmonic_sums
        from ..peaks import cluster_peaks_device, find_peaks_device

        nlev = nharms + 1
        rng = np.random.default_rng(0)
        # sub-threshold noise + planted combs: the crossing count is
        # set by the combs alone (a few hundred), so the jnp oracle's
        # fixed raw compaction below never overflows at ANY nbins;
        # values vary across the full spectrum so every stream's gather
        # path is data-sensitive
        s = np.abs(rng.normal(size=(9, nbins))).astype(np.float32)
        s[::3, :: max(1, nbins // 97)] += 30.0
        s[1, nbins // 2 : nbins // 2 + 400 : 4] += 20.0
        lo, hi = nbins // 10, nbins - nbins // 16
        windows = np.tile(np.asarray([[lo, hi]], np.int32), (nlev, 1))
        npad = -(-nbins // PEAKS_BLOCK) * PEAKS_BLOCK
        # pad region: huge garbage, like the production fused path can
        # carry past the true bins — must be masked by the hi clamp
        sp = jnp.asarray(
            np.pad(s, ((0, 0), (0, npad - nbins)), constant_values=1e9)
        )
        scales = tuple(
            1.0 if lv == 0 else 2.0 ** (-lv / 2.0) for lv in range(nlev)
        )
        # traced, as the search runs it: called eagerly, the kernel's
        # program takes one argument per stream view of the spectrum
        # (46 copies at 4 harmonics), past HBM above 2^22 bins
        ci, cs, rc, cc = jax.jit(
            lambda x, w: find_harmonic_cluster_peaks(
                x, w, nharms=nharms, threshold=9.0, max_peaks=max_peaks,
                scales=scales, nbins=nbins,
            )
        )(sp, jnp.asarray(windows))
        ci, cs, rc, cc = map(np.asarray, (ci, cs, rc, cc))
        levels = [jnp.asarray(s)] + harmonic_sums(
            jnp.asarray(s), nharms=nharms, method="take", scaled=True
        )
        ok = True
        for lv in range(nlev):
            if not ok:
                break
            i_, s_, c_ = find_peaks_device(
                levels[lv], jnp.float32(9.0), jnp.int32(lo), jnp.int32(hi),
                max_peaks=1 << 14,
            )
            ji, js, jc = cluster_peaks_device(i_, s_, jnp.int32(nbins))
            ji, js, jc, c_ = map(np.asarray, (ji, js, jc, c_))
            ok = np.array_equal(rc[:, lv], c_) and np.array_equal(
                cc[:, lv], jc
            )
            for r in range(s.shape[0]):
                if not ok:
                    break
                k = min(int(jc[r]), max_peaks)
                ok = np.array_equal(
                    ci[r, lv, :k], ji[r, :k]
                ) and np.array_equal(cs[r, lv, :k], js[r, :k])
        return _oracle(ok)


@lru_cache(maxsize=None)
def probe_pallas_dftspec(n: int, npad: int) -> bool:
    """REAL compile+run probe of the fused four-step DFT + untwist +
    interbin + normalise kernel (ops/pallas/dftspec.py) at the
    PRODUCTION (n, npad) — the DFT factorisation (n1, n2) is shape-
    dependent, so unlike the other probes this one runs the exact
    production geometry. Two deliberate gates (the kernel is 3-pass
    HIGH-class, so a single bitwise-vs-exact-chain gate is impossible
    by construction):

    (a) STRUCTURAL, per bin vs dft_untwist_interbin_twin — the same
        helpers with the same term grouping run outside Pallas — at
        |got - twin| <= 3e-5 (|twin| + rms): Mosaic's MXU accumulation
        order differs from XLA's by at most 8.9e-6 of that envelope
        (measured, v5e, production shape), while a broken lowering
        (roll off by a lane, bad flip, wrong clamp) perturbs bins by
        O(rms) — five orders above the gate — and fails every bin it
        breaks.
    (b) ACCURACY CLASS, vs the exact Precision.HIGHEST einsum chain on
        tone+noise data: per-bin |amp - amp_ref| / (|amp_ref| + rms)
        max <= 1e-3 and 99.9%-quantile <= 2e-4 (measured 3.7e-4 /
        5.7e-5; the max sits at untwist-cancellation bins adjacent to
        the tone, inherent to any HIGH-class DFT). The golden-recall
        gate (tests/test_recall.py) remains the end-to-end arbiter.
    """
    if not backend_supports_pallas():
        return False
    with _on_chip("dftspec", n=n, npad=npad):
        import numpy as np
        import jax.numpy as jnp

        from .dftspec import (
            ACC_MAX_REL, ACC_Q999_REL, accuracy_rel,
            dft_untwist_interbin, dft_untwist_interbin_twin,
            dftspec_supported, oracle_data, twin_envelope,
        )
        from ..fft import rfft_pow2_matmul_parts
        from ..spectrum import form_interpolated_parts, normalise

        if not dftspec_supported(n, npad):
            return False
        m = n // 2
        x, xe, xo, mean, std = oracle_data(n)
        xe, xo = jnp.asarray(xe), jnp.asarray(xo)
        meanj, stdj = jnp.asarray(mean), jnp.asarray(std)
        got = np.asarray(
            dft_untwist_interbin(xe, xo, meanj, stdj, npad=npad)
        )
        tw = np.asarray(
            dft_untwist_interbin_twin(xe, xo, meanj, stdj, npad=npad)
        )
        ok = got.shape == (9, npad) and bool(
            (np.abs(got - tw) <= twin_envelope(tw)).all()
        )
        if ok:
            ref = np.asarray(
                normalise(
                    form_interpolated_parts(
                        *rfft_pow2_matmul_parts(jnp.asarray(x))
                    ),
                    meanj, stdj,
                )
            )
            rel = accuracy_rel(got, ref, mean, std, m)
            ok = (
                float(rel.max()) <= ACC_MAX_REL
                and float(np.quantile(rel, 0.999)) <= ACC_Q999_REL
                and not got[:, m + 1 :].any()
            )
        return _oracle(ok)


@lru_cache(maxsize=None)
def probe_pallas_boxcar(n_widths: int, span: int) -> bool:
    """REAL compile+run probe of the single-pulse boxcar sweep kernel
    (ops/pallas/boxcar.py) at the production width count and tile span,
    gated on BITWISE equality with the jnp twin
    (ops.singlepulse.boxcar_best_twin): both consume the same padded
    prefix-sum rows and replay the same f32 subtract/scale/mask/max
    chain, so any difference means a broken lowering (roll off by a
    lane, bad SMEM scalar read, mis-clamped window). The features that
    vary by toolchain (dynamic-offset 1-D DMA, dynamic pltpu.roll,
    scalar-prefetch SMEM reads) are exercised at a reduced trial count
    with the production (n_widths, span) geometry."""
    if not backend_supports_pallas() or span <= 0:
        return False
    with _on_chip("boxcar", n_widths=n_widths, span=span):
        import numpy as np
        import jax.numpy as jnp

        from .boxcar import boxcar_best_pallas
        from ..singlepulse import (
            boxcar_best_twin,
            default_widths,
            prefix_sum_padded,
            width_extent,
            width_scales,
        )

        widths = default_widths(n_widths)
        scales = width_scales(widths)
        tpad = 2 * span
        wext = width_extent(widths)
        rng = np.random.default_rng(0)
        nvalid = tpad - span // 2  # exercise the validity tail mask
        norm = rng.normal(size=(3, nvalid)).astype(np.float32)
        # a planted bright pulse makes the argmax width data-sensitive
        norm[1, nvalid // 3 : nvalid // 3 + 16] += 25.0
        csum = prefix_sum_padded(jnp.asarray(norm), tpad, wext)
        got_b, got_w = boxcar_best_pallas(
            csum, widths, scales, nvalid, tpad, span=span
        )
        ref_b, ref_w = boxcar_best_twin(csum, widths, scales, nvalid, tpad)
        ok = bool(
            np.array_equal(np.asarray(got_b), np.asarray(ref_b))
            and np.array_equal(np.asarray(got_w), np.asarray(ref_w))
        )
        return _oracle(ok)


@lru_cache(maxsize=None)
def probe_pallas_spchain(n_widths: int, span: int, dec: int) -> bool:
    """REAL compile+run probe of the fused single-pulse chain tail
    (ops/pallas/spchain.py: boxcar sweep + dec-fold in one VMEM pass)
    at the production width count, tile span and decimation, gated on
    BITWISE equality with the jnp twin
    (ops.singlepulse.boxcar_dec_best_twin). Beyond the boxcar kernel's
    feature set this needs the (span/dec, dec) retile of the sweep
    tile, which the kernel makes by a transpose (spchain.fold_fits)."""
    from .spchain import fold_fits

    if not backend_supports_pallas() or span <= 0 or dec <= 0:
        return False
    if not fold_fits(span, dec):
        return False
    with _on_chip("spchain", n_widths=n_widths, span=span, dec=dec):
        import numpy as np
        import jax.numpy as jnp

        from .spchain import boxcar_dec_best_pallas
        from ..singlepulse import (
            boxcar_dec_best_twin,
            default_widths,
            prefix_sum_padded,
            width_extent,
            width_scales,
        )

        widths = default_widths(n_widths)
        scales = width_scales(widths)
        tpad = 2 * span
        wext = width_extent(widths)
        rng = np.random.default_rng(0)
        nvalid = tpad - span // 2  # exercise the validity tail mask
        norm = rng.normal(size=(3, nvalid)).astype(np.float32)
        # a planted bright pulse makes argmax/width data-sensitive; a
        # duplicated value exercises the first-max tie rule
        norm[1, nvalid // 3 : nvalid // 3 + 16] += 25.0
        norm[2, 100] = norm[2, 100 + dec // 2] = 30.0
        csum = prefix_sum_padded(jnp.asarray(norm), tpad, wext)
        got = boxcar_dec_best_pallas(
            csum, widths, scales, nvalid, tpad, dec, span=span
        )
        ref = boxcar_dec_best_twin(csum, widths, scales, nvalid, tpad, dec)
        ok = all(
            np.array_equal(np.asarray(g), np.asarray(r))
            for g, r in zip(got, ref)
        )
        return _oracle(ok)


@lru_cache(maxsize=None)
def probe_pallas_specchain() -> bool:
    """REAL compile+run probe of the fused deredden+zap+interbin kernel
    (ops/pallas/specchain.py) at a small shape, gated on BITWISE
    equality with the jnp twin (ops.spectrum.interp_deredden_zap): the
    kernel replays the same f32 divide/select/square/max/sqrt chain,
    so any difference means a broken lowering (carry off by a tile,
    roll off by a lane, bad mask). The varying features (static
    pltpu.roll, VMEM carry scratch, scalar-prefetch bins count) are
    shape-independent, so one probe at the production SPEC_BLOCK
    gates every production shape."""
    if not backend_supports_pallas():
        return False
    with _on_chip("specchain"):
        import numpy as np
        import jax.numpy as jnp

        from .specchain import SPEC_BLOCK, interp_deredden_zap_pallas
        from ..spectrum import interp_deredden_zap

        rng = np.random.default_rng(0)
        nbins = SPEC_BLOCK + SPEC_BLOCK // 2 + 1  # odd, forces the pad
        d = 9  # forces the row pad
        re = jnp.asarray(rng.normal(size=(d, nbins)).astype(np.float32))
        im = jnp.asarray(rng.normal(size=(d, nbins)).astype(np.float32))
        med = jnp.asarray(
            (0.5 + rng.random((d, nbins))).astype(np.float32)
        )
        zap = np.zeros(nbins, dtype=bool)
        zap[40:44] = True
        zap[2] = True  # a birdie inside the zeroed low bins
        zap[SPEC_BLOCK - 1 : SPEC_BLOCK + 1] = True  # tile boundary
        zapj = jnp.asarray(zap)
        got = interp_deredden_zap_pallas(re, im, med, zapj)
        ref = interp_deredden_zap(re, im, med, zapj)
        # parts are pure select/divide chains: BITWISE. The amplitude
        # carries the mul+add sums whose only legitimate deviation is
        # FMA-contraction codegen: per-bin envelope (s0_envelope), the
        # dftspec/interbin discipline — a structural fault (bad carry,
        # shifted lane) perturbs bins by O(rms), orders above it
        from .specchain import s0_envelope

        s0_got, s0_ref = np.asarray(got[2]), np.asarray(ref[2])
        ok = all(
            np.array_equal(np.asarray(g), np.asarray(r))
            for g, r in zip(got[:2], ref[:2])
        ) and bool(
            (np.abs(s0_got - s0_ref) <= s0_envelope(s0_ref)).all()
        )
        return _oracle(ok)


from .resample import resample_block_pallas, resample_block  # noqa: E402


@lru_cache(maxsize=None)
def probe_pallas_dedisperse() -> bool:
    """REAL compile+run probe of the dedispersion kernel (cached per
    process). Small-shape oracle check: the features that vary by
    toolchain (dynamic-offset 1-D DMA, dynamic pltpu.roll, SMEM scalar
    reads) are shape-independent, so one small probe gates the kernel
    for every production shape."""
    if not backend_supports_pallas():
        return False
    with _on_chip("dedisperse"):
        import numpy as np
        import jax.numpy as jnp

        from .dedisperse import dedisperse_pallas
        from ..dedisperse import dedisperse_block

        rng = np.random.default_rng(0)
        t, c, d = 8192, 16, 8
        fil = rng.integers(0, 4, size=(t, c)).astype(np.uint8)
        # irregular delays exercise every rem/roll combination
        delays = np.sort(
            rng.integers(0, 3000, size=(d, c)).astype(np.int32), axis=0
        )
        kill = (rng.random(c) > 0.2).astype(np.int32)
        out_nsamps = t - int(delays.max())
        got = np.asarray(
            dedisperse_pallas(fil, delays, kill, out_nsamps, scale=0.9)
        )
        ref = np.asarray(
            dedisperse_block(
                jnp.asarray(fil), jnp.asarray(delays), jnp.asarray(kill),
                out_nsamps=out_nsamps, scale=0.9,
            )
        )
        return _oracle(bool(np.array_equal(got, ref)))
