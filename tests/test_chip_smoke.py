"""chip_smoke.py and the compile cache: nothing quietly leaves
the chip, and the caller places the cache.

The smoke's real run is on the chip (``python chip_smoke.py`` through the
builder's tool); here its control flow is rehearsed on the CPU at SF0.01.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, timeout, **env):
    full = {**os.environ, "JAX_PLATFORMS": "cpu", **env}
    return subprocess.run([sys.executable] + cmd, cwd=REPO, env=full,
                          capture_output=True, text=True, timeout=timeout)


def test_smoke_refuses_a_cpu():
    out = _run(["chip_smoke.py", "--scale", "0.01"], 300)
    assert out.returncode != 0
    assert "no TPU" in out.stdout + out.stderr
    assert '"ok": true' not in out.stdout


def test_smoke_rehearsal_compares_every_phase_and_is_not_a_success_line():
    out = _run(["chip_smoke.py", "--allow-cpu", "--scale", "0.01"], 600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    runs = [json.loads(ln) for ln in lines if ln.startswith('{"phase"')]
    compared = {(r["phase"], r["query"], r["run"]) for r in runs
                if r.get("equal_to_oracle")}
    want = {("standalone", f"q{q}", run) for q in (1, 6, 3, 18)
            for run in ("cold", "warm")}
    want |= {("cluster", "q6", "cold"), ("cluster", "q3", "cold")}
    assert compared == want
    # the three-process phase: only the executor names a device, and the
    # client started no backend but the CPU's
    assert any(r.get("client_backends") == ["cpu"] for r in runs)
    assert "scheduler up, on the CPU platform" in out.stdout
    assert "executor up: cpu/" in out.stdout


def test_smoke_parent_fails_when_a_child_fails():
    """The parent imports neither jax nor the package, so it can be driven
    here: a child's non-zero exit must end the run."""
    code = ("import chip_smoke, sys; "
            "assert 'jax' not in sys.modules; "
            "assert 'arrow_ballista_tpu' not in sys.modules; "
            "chip_smoke.run_child('failing', [sys.executable, '-c', "
            "'import sys; print(\"{}\"); sys.exit(3)'], chip_smoke._env(), 30)")
    out = _run(["-c", code], 60)
    assert out.returncode != 0
    assert "phase failing: child exited 3" in out.stderr


@pytest.mark.parametrize("placed", [True, False], ids=["env", "default"])
def test_compilation_cache_is_where_the_caller_puts_it(tmp_path, placed):
    """JAX_COMPILATION_CACHE_DIR set: that directory and no other.  Unset:
    one fixed directory in the checkout, whatever the platform or process."""
    code = ("import arrow_ballista_tpu, jax, jax.numpy as jnp; "
            "jax.config.update("
            "'jax_persistent_cache_min_compile_time_secs', 0); "
            "jax.jit(lambda x: jnp.sort(x) * 3)(jnp.arange(4099)); "
            "print(jax.config.jax_compilation_cache_dir)")
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} if placed else {}
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         env={**base, "JAX_PLATFORMS": "cpu", **env},
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    want = str(tmp_path) if placed else os.path.join(REPO, ".xla_cache")
    assert out.stdout.strip().splitlines()[-1] == want
    assert os.listdir(want), "the compiled program was not written there"


def test_importing_the_engine_starts_no_backend():
    """The scheduler daemon and the remote client pin themselves to the CPU
    platform at start; that only works while no import has started a
    backend on the way."""
    code = ("import arrow_ballista_tpu.client.context, "
            "arrow_ballista_tpu.client.remote, "
            "arrow_ballista_tpu.scheduler.netservice, "
            "arrow_ballista_tpu.executor.server; "
            "from jax._src import xla_bridge; "
            "assert not xla_bridge.backends_are_initialized()")
    out = _run(["-c", code], 120)
    assert out.returncode == 0, out.stderr[-2000:]
