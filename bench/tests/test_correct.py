"""``correct`` on small cells on the CPU: a sound run passes, the
control and every planted fault of the timed path fail."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import check, generator, harness
from bench.tests.conftest import ROOT

DATA = ROOT / "bench" / "tests" / "data"
DRIVER = ROOT / "bench" / "tests" / "fault_run.py"


def run_cell(fault, workload, seed, trace=0):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if workload.endswith("-4"):
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    p = subprocess.run(
        [sys.executable, str(DRIVER), fault, "--workload", workload,
         "--seed", str(seed), "--seconds", "1", "--trace", str(trace)],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,trace", [("tiny-1", 0), ("tiny-4", 1),
                                            ("tiny-dec-1", 0)])
def test_sound_run_is_correct(workload, trace):
    out = run_cell("none", workload, 2 ** 31 + 17, trace)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(check.NAMES)
    want = ({"input_ms", "device_idle_share"} if trace
            else {"tokens_per_s", "setup_s"})
    assert want <= set(out["metrics"])
    if trace:
        assert out["device"]["busy_s"] > 0
        assert out["breakdown"]["device_ops"]


@pytest.mark.parametrize("fault,workload", [
    ("state_unchanged", "tiny-1"), ("half_batch", "tiny-1"),
    ("dup_overwrite", "tiny-1"), ("state_unchanged", "tiny-4"),
    ("half_batch", "tiny-4"), ("no_exchange", "tiny-4"),
    ("dup_overwrite", "tiny-4"), ("half_batch", "tiny-dec-1"),
    ("dup_overwrite", "tiny-dec-1")])
def test_fault_is_not_correct(fault, workload):
    out = run_cell(fault, workload, 23)
    assert out["correct"] is False, out["checks"]


def control_and_sound(config, traffic, workload, seed):
    """The float8 control's verdict and the reference's own, against
    the cell's limits."""
    import jax
    cfg = json.loads((DATA / "configs" / f"{config}.json").read_text())
    tr = generator.load(traffic, DATA)
    pool = generator.make_pool(tr, cfg, seed, 1)
    devs = jax.devices()[:1]
    warmup = tr["launcher"]["warmup"]
    ref = harness.reference_readings(cfg, seed, pool, warmup, 1, devs)
    ctl = harness.reference_readings(cfg, seed, pool, warmup, 1, devs,
                                     "fp8")
    limits = check.load_limits(workload, DATA)
    return (check.judge(check.gaps(ctl, ref), limits)["correct"],
            check.judge(check.gaps(ref, ref), limits)["correct"])


@pytest.mark.parametrize("seed", [31, 32, 33])
def test_control_is_not_correct(seed):
    """The reference in float8 put in the program's place fails the
    limits that the program passes."""
    assert control_and_sound("tiny", "tiny_dp1", "tiny-1", seed) == \
        (False, True)


@pytest.mark.parametrize("seed", [31, 32, 33])
def test_control_is_not_correct_without_frontend(seed):
    """The same for the decoder without cross-attention, untied."""
    assert control_and_sound("tiny-dec", "tiny_dp1", "tiny-dec-1",
                             seed) == (False, True)


def test_no_chip_no_result(tmp_path):
    """Pinned to the CPU, and in a checkout that holds only the
    benchmark, a run exits non-zero and prints no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for cwd in (ROOT, tmp_path):
        p = subprocess.run(
            [sys.executable, "bench/run.py", "--workload",
             "tbig-train-1chip", "--seed", "1", "--seconds", "1"],
            capture_output=True, text=True, cwd=cwd, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert p.returncode != 0 and p.stdout.strip() == "", p.stdout


def test_device_not_in_the_peak_table_fails(monkeypatch, capsys):
    """A chip whose kind has no row in bench/peaks.json is an error, not
    a default: exit 2, no result."""
    from bench import run
    monkeypatch.setattr(run, "pick_devices",
                        lambda jax, chips, allow_cpu: jax.devices()[:chips])
    assert run.main(["--workload", "tbig-train-1chip", "--seed", "1",
                     "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert peaks["devices"]["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
