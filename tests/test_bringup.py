"""Bring-up contract (ISSUE 21): nothing on the served path hides the
device.  The compile cache can be placed from outside, every compile
probe says why it said no, the in-place probe's no refuses the start,
and `chip_smoke.py` exits non-zero without a chip instead of carrying
on on the CPU.  (The `/debug/vars` `device` block rides
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
        if k not in ("JAX_COMPILATION_CACHE_DIR", "PYTHONPATH")
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

    monkeypatch.setattr(bk, "fused_step", _Refuses())
    monkeypatch.setattr(bk, "multi_fused_step", _Refuses())
    for probe in (bk.fused_step_ok, bk.multi_step_ok):
        verdict = probe.__wrapped__(4096)  # past the lru_cache
        assert verdict.ok is False
        assert verdict.reason == "RuntimeError: Mosaic says no"
    # An honest yes carries the numbers that decided it.
    monkeypatch.undo()
    yes = bk.fused_step_ok(4096)
    assert yes.ok and "temp" in yes.reason and "bound" in yes.reason


def _build_engine(which):
    """The engine's module, its constructor, the name of its in-place
    probe in `probes`, and how to make that probe say no."""
    from gubernator_tpu.ops.bucket_kernel import ProbeVerdict

    no = ProbeVerdict(False, "clones state")
    if which == "DecisionEngine":
        from gubernator_tpu.core import engine as mod

        return (
            lambda: mod.DecisionEngine(capacity=64),
            "fused_step",
            lambda mp: mp.setattr(mod, "fused_step_ok", lambda cap: no),
        )
    from gubernator_tpu.parallel import sharded_engine as mod

    single = which.endswith("single_program")
    return (
        lambda: mod.ShardedDecisionEngine(
            shard_capacity=64, single_program=single
        ),
        "mesh_step",
        lambda mp: mp.setattr(
            mod.ShardedDecisionEngine, "_mesh_step_ok", lambda self: no
        ),
    )


@pytest.mark.parametrize(
    "which",
    [
        "DecisionEngine",
        "ShardedDecisionEngine",
        "ShardedDecisionEngine.single_program",
    ],
)
def test_in_place_probes_no_refuses_the_start_with_its_reason(
    which, monkeypatch
):
    """There is one step family: where the donated step does not
    compile in place on an accelerator, neither engine starts on a
    second program — it raises with the probe's reason."""
    import jax

    build, name, say_no = _build_engine(which)
    yes = build().probes[name]
    assert yes.ok and "temp" in yes.reason
    say_no(monkeypatch)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(
        RuntimeError, match=rf"{name} probe said no \(clones state\)"
    ):
        build()


def test_on_the_cpu_backend_the_no_is_logged_and_the_same_program_serves(
    caplog,
):
    """XLA:CPU clones half the columns at every size, which the probe
    reads as a no from 43,691 rows on (the default 50,000 among them):
    recorded, logged, and the one step program serves — one dispatch
    a batch, the answer the small table gives."""
    from gubernator_tpu import RateLimitReq
    from gubernator_tpu.core.engine import DecisionEngine

    with caplog.at_level(logging.WARNING, logger="gubernator_tpu.engine"):
        e = DecisionEngine()  # the default capacity, upstream's
    no = e.probes["fused_step"]
    assert not no.ok and ">= bound" in no.reason
    assert f"fused_step probe said no ({no.reason})" in caplog.text
    req = [RateLimitReq(name="a", unique_key="b", hits=1, limit=5, duration=1000)]
    e.get_rate_limits(req)
    before = e.dispatches_total
    (r,) = e.get_rate_limits(req)
    assert r.remaining == 3 and e.dispatches_total - before == 1


def test_multi_step_probes_no_is_recorded_logged_and_serves_per_round(
    monkeypatch, caplog
):
    """The scan probe's no is a choice between two served arms: it is
    kept in `probes`, logged with its reason, and the engine serves
    without the pump."""
    from gubernator_tpu import RateLimitReq
    from gubernator_tpu.core import engine as eng
    from gubernator_tpu.ops.bucket_kernel import ProbeVerdict

    monkeypatch.setenv("GUBER_PUMP", "1")
    monkeypatch.setattr(
        eng, "multi_step_ok", lambda cap: ProbeVerdict(False, "scan clones")
    )
    with caplog.at_level(logging.WARNING, logger="gubernator_tpu.engine"):
        e = eng.DecisionEngine(capacity=64)
    assert e._pump is None
    assert e.probes["multi_step"] == ProbeVerdict(False, "scan clones")
    assert "multi_step probe said no (scan clones)" in caplog.text
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

