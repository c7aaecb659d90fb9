"""Bring-up contract (ISSUE 21): nothing on the served path hides the
device.  The compile cache can be placed from outside, every compile
probe says why it said no, `GUBER_FUSED=pallas` refuses loudly, and
`chip_smoke.py` / `bench.py` exit non-zero without a chip instead of
carrying on on the CPU.  (The `/debug/vars` `device` block rides
tests/test_trace_stitch.py's existing daemon.)"""

from __future__ import annotations

import logging
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


_CACHE_PROBE = (
    "import jax, gubernator_tpu; "
    "print(jax.config.jax_compilation_cache_dir); "
    "print(jax.config.jax_persistent_cache_min_compile_time_secs)"
)


@pytest.fixture(scope="module")
def children(tmp_path_factory):
    """Every subprocess this module judges, started together so that
    tier-1 pays for the slowest one only (name → finished process)."""
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_COMPILATION_CACHE_DIR", "PYTHONPATH",
                     "BENCH_FORCE_CPU")
    }
    # Copies of the tree without and with a .git directory (the package
    # by symlink: its location is computed without resolving links).
    trees = {}
    for name in ("nogit", "git"):
        tree = tmp_path_factory.mktemp(name)
        os.symlink(os.path.join(ROOT, "gubernator_tpu"), tree / "gubernator_tpu")
        if name == "git":
            (tree / ".git").mkdir()
        trees[name] = tree
    alone = tmp_path_factory.mktemp("alone")
    with open(os.path.join(ROOT, "chip_smoke.py"), "rb") as f:
        (alone / "chip_smoke.py").write_bytes(f.read())
    probe = [sys.executable, "-c", _CACHE_PROBE]
    specs = {
        "cache_nogit": (probe, trees["nogit"], env),
        "cache_git": (probe, trees["git"], env),
        "cache_placed": (
            probe, trees["nogit"],
            dict(env, JAX_COMPILATION_CACHE_DIR="/some/dir"),
        ),
        "smoke": ([sys.executable, "chip_smoke.py"], ROOT, env),
        "smoke_alone": ([sys.executable, "chip_smoke.py"], alone, env),
        "bench": ([sys.executable, "bench.py"], ROOT, env),
        "bench_failed_run": (
            [sys.executable, "bench.py"], ROOT,
            dict(env, BENCH_FORCE_CPU="1", BENCH_MODE="sketch",
                 BENCH_WIRE_FAST="1"),
        ),
    }
    procs = {
        name: subprocess.Popen(
            cmd, cwd=cwd, env=e, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
        )
        for name, (cmd, cwd, e) in specs.items()
    }
    done = {}
    for name, p in procs.items():
        out, err = p.communicate(timeout=300)
        done[name] = subprocess.CompletedProcess(p.args, p.returncode, out, err)
    done["trees"] = trees
    return done


def test_cache_dir_is_checkout_relative_or_placed_from_outside(children):
    """Unset, the cache is <checkout>/.jax_cache, computed from the
    package's own location whether or not a .git directory exists;
    JAX_COMPILATION_CACHE_DIR, where set, is left alone."""
    for name in ("nogit", "git"):
        out = children[f"cache_{name}"]
        assert out.returncode == 0, out.stderr
        assert out.stdout.split() == [
            str(children["trees"][name] / ".jax_cache"), "0.0",
        ]
    out = children["cache_placed"]
    assert out.returncode == 0, out.stderr
    assert out.stdout.split()[0] == "/some/dir"


class _Refuses:
    """A jitted step whose compile the backend refuses."""

    def lower(self, *a, **k):
        raise RuntimeError("Mosaic says no\nsecond line")


def test_probes_report_the_compilers_reason(monkeypatch):
    from gubernator_tpu.ops import bucket_kernel as bk
    from gubernator_tpu.ops import pallas_step as ps

    monkeypatch.setattr(bk, "fused_step", _Refuses())
    monkeypatch.setattr(bk, "multi_fused_step", _Refuses())
    monkeypatch.setattr(ps, "_jitted_step", lambda *a: _Refuses())
    for probe in (bk.fused_step_ok, bk.multi_step_ok, ps.pallas_step_ok):
        verdict = probe.__wrapped__(4096)  # past the lru_cache
        assert verdict.ok is False
        assert verdict.reason == "RuntimeError: Mosaic says no"
    # An honest yes carries the numbers that decided it.
    monkeypatch.undo()
    yes = bk.fused_step_ok(4096)
    assert yes.ok and "temp" in yes.reason and "bound" in yes.reason


def test_engine_records_and_logs_a_probes_no(monkeypatch, caplog):
    from gubernator_tpu.core import engine as eng
    from gubernator_tpu.ops.bucket_kernel import ProbeVerdict

    monkeypatch.setenv("GUBER_FUSED", "xla")
    monkeypatch.setattr(
        eng, "fused_step_ok", lambda cap: ProbeVerdict(False, "clones state")
    )
    with caplog.at_level(logging.WARNING, logger="gubernator_tpu.engine"):
        e = eng.DecisionEngine(capacity=64)
    assert e.fused_mode == "split" and e._pump is None
    assert e.probes["fused_step"] == ProbeVerdict(False, "clones state")
    assert "fused_step probe said no (clones state)" in caplog.text


def test_fused_pallas_raises_where_refused_interpret_still_runs(monkeypatch):
    from gubernator_tpu import RateLimitReq
    from gubernator_tpu.core.engine import DecisionEngine

    monkeypatch.setenv("GUBER_FUSED", "pallas")
    with pytest.raises(ValueError, match="interpret mode"):
        DecisionEngine(capacity=64)  # XLA:CPU refuses the compiled kernel
    monkeypatch.setenv("GUBER_FUSED", "interpret")
    e = DecisionEngine(capacity=64)
    assert e.fused_mode == "pallas-interpret"
    (r,) = e.get_rate_limits(
        [RateLimitReq(name="a", unique_key="b", hits=1, limit=5, duration=1000)]
    )
    assert r.remaining == 4


def test_chip_smoke_without_a_tpu_fails_and_names_the_platform(children):
    out = children["smoke"]
    assert out.returncode != 0
    assert "jax, left to choose, finds: cpu" in out.stdout
    assert '"ok"' not in out.stdout  # no result line
    # Alone in a directory it fails too (nothing of the repo to import).
    out = children["smoke_alone"]
    assert out.returncode != 0 and '"ok"' not in out.stdout


def test_chip_smoke_result_line_has_exactly_the_contract_keys():
    import json

    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    summary = {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "engine": "DecisionEngine", "claim": None,
    }
    assert json.loads(chip_smoke.result_line(summary)) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


def test_bench_exits_nonzero_without_a_chip_or_on_a_failed_run(children):
    out = children["bench"]
    assert out.returncode != 0
    assert "no accelerator" in out.stderr and '"value"' not in out.stdout
    # A run that fails still prints its one JSON line — and exits non-zero.
    out = children["bench_failed_run"]
    assert out.returncode != 0 and '"error"' in out.stdout
