"""CPU rehearsal of chip_smoke.py at a tiny size.

The script itself only ever runs on a TPU; these tests steer its pieces
here: a phase end to end through the peasoup CLI, the -t 4 against -t 1
comparison on virtual devices, the kernel-route check, and the refusal
to run without a chip.
"""

import os
import subprocess
import sys

import pytest

import chip_smoke

TINY = chip_smoke.Phase(
    "tiny", nchans=16, nsamps=1 << 15, tsamp=256e-6, fch1=1400.0,
    foff=-8.0, period=0.064, dm=20.0, seed=7, duty=0.06, amp=1.0,
    flags=("--dm_end", "40", "--acc_start", "-2", "--acc_end", "2",
           "--npdmp", "2"),
    dm_tol=10.0,
)
ROUTE_ON_TPU = {
    "backend": "tpu", "pallas_peaks": True, "mega_harm": True,
    "fused_interbin": True, "fused_dft": True, "fused_spec": True,
    "resample_block": 0, "interbin_fits": True, "dftspec_fits": True,
    "select_smax": 1, "resample_fits": False,
}


@pytest.fixture
def work(tmp_path, monkeypatch):
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path))
    return tmp_path


def test_phase_recovers_the_injected_pulsar(work):
    rep = chip_smoke.run_phase(TINY, expect_tpu=False)
    assert abs(rep["top"]["period"] / TINY.period - 1) < 2e-3
    assert rep["route"]["backend"] == "cpu"
    assert rep["dedisp_engines"] == ["scan"]
    assert rep["compiles"] > 0 and rep["wall_s"] > 0
    assert not (work / "tiny.fil").exists()  # inputs never outlive a phase


def test_sharded_and_single_chip_candidates_agree(work, monkeypatch):
    """The --multichip comparison on four virtual CPU devices (the CLI
    only shards on TPU by itself, so the device pick is steered)."""
    import jax

    from peasoup_tpu.pipeline.search import PeasoupSearch

    monkeypatch.setattr(
        PeasoupSearch, "_pick_devices",
        lambda self: jax.local_devices()[: self.config.max_num_threads],
    )
    chip_smoke.run_phase(
        TINY, threads=4, tag="_t4", expect_tpu=False, keep_fil=True
    )
    chip_smoke.run_phase(TINY, threads=1, tag="_t1", expect_tpu=False)
    cmp = chip_smoke.compare_candidates("tiny_t4", "tiny_t1")
    assert cmp["same_dm_list"] and cmp["same_rows"]
    assert cmp["same_candidates_file"]
    assert cmp["n_candidates"][0] == cmp["n_candidates"][1] > 0


def test_route_check_names_kernels_that_did_not_run():
    pallas = [{"engine": "pallas", "ndm": 59, "nchans": 64, "fits": True}]
    assert chip_smoke.route_failures(ROUTE_ON_TPU, pallas) == []
    off = dict(ROUTE_ON_TPU, mega_harm=False, fused_dft=False)
    scan = [{"engine": "scan", "ndm": 59, "nchans": 64, "fits": True}]
    assert chip_smoke.route_failures(off, scan) == [
        "mega_harm", "fused_dft", "dedisperse(59x64)",
    ]
    # a shape that does not fit a kernel is a route, not a failure
    big = [{"engine": "scan", "ndm": 9, "nchans": 9, "fits": False}]
    no_fit = dict(ROUTE_ON_TPU, dftspec_fits=False, fused_dft=False)
    assert chip_smoke.route_failures(no_fit, big) == []


def test_refuses_to_run_without_a_tpu():
    """Exit non-zero and print no result when JAX finds no TPU."""
    proc = subprocess.run(
        [sys.executable, chip_smoke.__file__],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "needs 1 TPU" in proc.stderr


def test_synth_input_is_named_by_its_parameters(tmp_path):
    """The smoke's and bench's inputs are written once per parameter
    set: the same parameters reuse the file, any change gets its own."""
    from peasoup_tpu.io.synth import pulsar_fil

    kw = dict(nchans=8, nsamps=4096, tsamp=256e-6, fch1=1500.0,
              foff=-1.0, period=0.05, dm=10.0, seed=1)
    a = pulsar_fil(str(tmp_path), **kw)
    mtime = os.stat(a).st_mtime_ns
    assert pulsar_fil(str(tmp_path), **kw) == a
    assert os.stat(a).st_mtime_ns == mtime
    assert pulsar_fil(str(tmp_path), **dict(kw, amp=0.5)) != a
    assert len(os.listdir(tmp_path)) == 2
