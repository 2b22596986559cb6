"""Fault tolerance: SIGKILL a training run mid-flight; auto-resume must
continue from the last COMMITted checkpoint and reach the same final state
as an uninterrupted run (bit-exact: same data cursor, same step count)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run_train(ckpt_dir, steps, crash_at=0, auto_resume=True):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"   # tests never cache
    cmd = [sys.executable, "-m", "repro.launch.train",
           "--arch", "tinyllama-42m", "--smoke",
           "--steps", str(steps), "--batch", "2", "--seq-len", "32",
           "--ckpt-dir", ckpt_dir, "--ckpt-every", "5", "--log-every", "5"]
    if crash_at:
        cmd += ["--crash-at-step", str(crash_at)]
    if auto_resume:
        cmd += ["--auto-resume"]
    return subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=1200)


@pytest.mark.slow
def test_crash_resume_matches_uninterrupted(tmp_path):
    # uninterrupted reference
    r0 = _run_train(str(tmp_path / "ref"), steps=15)
    assert r0.returncode == 0, r0.stderr[-2000:]
    ref_line = [ln for ln in r0.stdout.splitlines()
                if ln.startswith("step    15")]
    assert ref_line, r0.stdout

    # crashed at step 8 (checkpoint exists at 5), then resumed
    r1 = _run_train(str(tmp_path / "ft"), steps=15, crash_at=8)
    assert r1.returncode == 17          # fault injection exit
    assert "[fault-injection]" in r1.stdout
    r2 = _run_train(str(tmp_path / "ft"), steps=15)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "[resume] step 5" in r2.stdout
    res_line = [ln for ln in r2.stdout.splitlines()
                if ln.startswith("step    15")]
    assert res_line, r2.stdout

    # same final loss (same params/opt/data stream => identical trajectory)
    def loss_of(line):
        return float(line[0].split("loss")[1].split()[0])
    assert abs(loss_of(ref_line) - loss_of(res_line)) < 1e-4


def test_supervisor_restarts_until_success(tmp_path):
    """runtime.ft.supervise restarts a failing command."""
    from repro.runtime.ft import FTConfig, supervise
    marker = tmp_path / "ran"
    script = (f"import os,sys; p=r'{marker}'; "
              "n=int(open(p).read()) if os.path.exists(p) else 0; "
              "open(p,'w').write(str(n+1)); sys.exit(0 if n>=2 else 1)")
    code = supervise([sys.executable, "-c", script],
                     FTConfig(max_restarts=5, restart_backoff_s=0.01))
    assert code == 0
    assert int(open(marker).read()) == 3


def test_hedged_router_mitigates_straggler():
    import time
    from repro.runtime.straggler import HedgedRouter
    calls = {"a": 0, "b": 0}

    def slow(req):
        calls["a"] += 1
        time.sleep(0.25)
        return ("slow", req)

    def fast(req):
        calls["b"] += 1
        return ("fast", req)

    router = HedgedRouter([slow, fast], hedge_after_s=0.03)
    out = router(42)
    assert out == ("fast", 42)          # hedge won
    assert router.stats.hedged == 1
