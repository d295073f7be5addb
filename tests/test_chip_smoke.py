"""chip_smoke.py's phases on the CPU at a tiny size, and its refusals.

The phase functions are the same code the chip run executes; here they
run small graphs through Pallas in interpret mode (pinned explicitly, so
the lowering check sees the Pallas kernel it asked for) and are held to
the NumPy oracle.  ``main()`` itself must refuse a host without a TPU.
"""

import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

import jax

from repro.core import MiningConfig, PTMTEngine, oracle
from repro.data import synthetic_graphs as sg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

PARAMS = dict(delta=60, l_max=4, omega=4)


@pytest.fixture(scope="module")
def engines():
    return {
        "default": PTMTEngine(MiningConfig(**PARAMS)),
        "pallas": PTMTEngine(MiningConfig(backend="pallas",
                                          fused_backend="pallas", **PARAMS)),
        "xla": PTMTEngine(MiningConfig(fused_backend="xla", **PARAMS)),
    }


@pytest.fixture(scope="module")
def email():
    return sg.make("email-eu-like", 0, n_edges=2000)


@pytest.fixture(scope="module")
def college():
    return sg.make("collegemsg-like", 0, n_edges=1500)


def _oracle(graph):
    return dict(oracle.count_codes(graph.u, graph.v, graph.t,
                                   PARAMS["delta"], PARAMS["l_max"]))


def test_mine_phase_matches_oracle(engines, email):
    counts = chip_smoke.phase_mine(email, engines,
                                   host_device=jax.devices("cpu")[0])
    assert counts == _oracle(email)


def test_mine_phase_takes_a_shared_oracle(engines, email):
    calls = []

    def oracle_counts():
        calls.append(1)
        return _oracle(email)

    prefix = chip_smoke.prefix(email, 1024)
    counts = chip_smoke.phase_mine(
        email, {"xla": engines["xla"]}, host_device=jax.devices("cpu")[0],
        oracle=oracle_counts, phase="mine-xla")
    assert counts == _oracle(email) and calls == [1]
    assert prefix.n_edges == 1024 and prefix.t[-1] == email.t[1023]


def test_exact_phase_matches_oracle(engines, college):
    counts = chip_smoke.phase_exact(college, engines["pallas"],
                                    engines["default"])
    assert counts == _oracle(college)


def test_stream_phase_matches_oracle(engines, email):
    counts = chip_smoke.phase_stream(email, engines["pallas"],
                                     lambda: _oracle(email), chunk_edges=256)
    assert counts == _oracle(email)


def test_serve_phase_answers_every_query_kind(engines, college):
    answers = chip_smoke.phase_serve(college, engines["pallas"],
                                     engines["default"])
    assert set(answers) == {"top_k", "transition_probs", "prefix_count",
                            "level_histogram"}
    assert min(answers.values()) >= chip_smoke.SERVE_ROUNDS


def test_phase_fails_on_wrong_counts(engines, email):
    wrong = dict(_oracle(email))
    wrong[next(iter(wrong))] += 1
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.phase_stream(email, engines["pallas"], lambda: wrong,
                                chunk_edges=512)


def test_lowering_check_refuses_a_reroute():
    cfg = MiningConfig(backend="pallas")
    assert chip_smoke.requested_lowering(cfg) == "pallas"
    assert chip_smoke.requested_lowering(MiningConfig()) is None
    rerouted = {"path": "fused_xla", "backend": "xla", "launches": 1}
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_lowering(cfg, rerouted)


def test_sharded_phase_on_four_virtual_devices(tmp_path):
    """The four-chip phase on 4 virtual CPU devices: sharded == one-chip ==
    oracle, and every bucket program precompiled before the sharded runs
    is found again in the persistent compilation cache."""
    code = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {REPO!r})
        import jax
        from jax import monitoring
        jax.config.update("jax_compilation_cache_dir", {str(tmp_path)!r})
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        hits = []
        monitoring.register_event_listener(
            lambda name, **kw: hits.append(name)
            if name == "/jax/compilation_cache/cache_hits" else None)
        import chip_smoke
        from repro.core import MiningConfig, PTMTEngine, oracle
        from repro.data import synthetic_graphs as sg
        p = {PARAMS!r}
        g = sg.make("email-eu-like", 0, n_edges=1500)
        pallas = PTMTEngine(MiningConfig(backend="pallas",
                                         fused_backend="pallas", **p))
        engines = {{"pallas": pallas,
                    "default": PTMTEngine(MiningConfig(**p))}}
        got = chip_smoke.phase_sharded(g, engines, pallas, jax.devices()[:4])
        assert got == dict(oracle.count_codes(g.u, g.v, g.t, p["delta"],
                                              p["l_max"]))
        n_programs = sum(len(e._plan_and_layout(g, n_shards=4)[1].buckets)
                         for e in engines.values())
        assert len(hits) >= n_programs, (len(hits), n_programs)
        print("SHARDED_OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env, cwd=tmp_path)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARDED_OK" in out.stdout


def test_every_one_chip_phase_passes_at_a_tiny_size(email, college):
    configs = {
        "default": MiningConfig(**PARAMS),
        "pallas": MiningConfig(backend="pallas", fused_backend="pallas",
                               **PARAMS),
        "xla": MiningConfig(fused_backend="xla", **PARAMS),
    }
    cpu = jax.devices("cpu")[0]
    assert chip_smoke.run_one_chip(email, college, configs=configs,
                                   host_device=cpu, device=cpu,
                                   xla_edges=800) == []


def test_run_phases_reports_each_failure():
    def broken():
        raise chip_smoke.SmokeFailure("wrong counts")

    failed = chip_smoke.run_phases(
        {"good": lambda: None, "bad": broken}, jax.devices()[0])
    assert failed == ["bad"]


def _run_smoke(script_dir, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, os.path.join(script_dir, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=script_dir)


def _printed_ok(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if json.loads(line).get("ok"):
                return True
        except (ValueError, AttributeError):
            continue
    return False


def test_main_refuses_a_host_without_tpu():
    out = _run_smoke(REPO)
    assert out.returncode != 0
    assert not _printed_ok(out.stdout)
    assert "no TPU" in out.stderr


def test_main_refuses_forced_interpret_mode():
    out = _run_smoke(REPO, REPRO_PALLAS_INTERPRET="1")
    assert out.returncode != 0
    assert not _printed_ok(out.stdout)


def test_main_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run_smoke(str(tmp_path))
    assert out.returncode != 0
    assert not _printed_ok(out.stdout)
