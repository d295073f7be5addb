"""MiningConfig: validation, precedence, serialization, CLI round-trip,
and the deprecated one-shot shims."""

import argparse
import dataclasses
import warnings

import pytest

from repro.core import MiningConfig, discover, discover_sequential
from repro.core.executor import AGG_MODES

from conftest import random_graph


# -- validation -------------------------------------------------------------

@pytest.mark.parametrize("bad", [
    dict(delta=0), dict(delta=-5), dict(l_max=0), dict(l_max=-1),
])
def test_nonpositive_delta_l_max_rejected(bad):
    with pytest.raises(ValueError, match="delta and l_max"):
        MiningConfig(**bad)


def test_omega_floor_rejected():
    with pytest.raises(ValueError, match="omega must be >= 2"):
        MiningConfig(omega=1)


def test_unknown_backend_rejected_with_listing():
    with pytest.raises(ValueError, match="unknown backend.*available"):
        MiningConfig(backend="no-such-backend")


def test_unknown_agg_mode_rejected():
    with pytest.raises(ValueError, match="agg"):
        MiningConfig(agg="bogus")


@pytest.mark.parametrize("bad", [
    dict(e_cap=0), dict(merge_cap=0), dict(zone_chunk=-1),
    dict(memory_budget_mb=0.0), dict(memory_budget_mb=-2.0),
])
def test_nonpositive_capacities_rejected(bad):
    with pytest.raises(ValueError):
        MiningConfig(**bad)


@pytest.mark.parametrize("bad", [
    dict(delta=599.9), dict(l_max=3.5), dict(e_cap=0.9),
])
def test_non_integral_values_rejected_not_truncated(bad):
    with pytest.raises(ValueError, match="must be an integer"):
        MiningConfig(**bad)
    # integral floats are fine and normalize to int
    assert MiningConfig(delta=600.0).delta == 600


def test_zone_chunk_beats_memory_budget_and_warns():
    """The one genuine conflict in the surface: explicit beats derived,
    loudly."""
    with pytest.warns(RuntimeWarning, match="zone_chunk takes precedence"):
        cfg = MiningConfig(delta=30, l_max=3, zone_chunk=4,
                           memory_budget_mb=64.0)
    from repro.core.executor import MiningExecutor

    ex = MiningExecutor.from_config(cfg)
    # the budget-derived plan is never consulted for the chunk
    assert ex._zone_chunk_for(1024, 128) == 4


def test_zone_chunk_zero_means_unchunked_even_with_budget():
    """zone_chunk=0 is an explicit 'unchunked' request — it beats the
    budget-derived chunk (and setting both warns) instead of silently
    falling through to budget-derived chunked mining."""
    from repro.core.executor import MiningExecutor

    with pytest.warns(RuntimeWarning, match="zone_chunk takes precedence"):
        cfg = MiningConfig(delta=30, l_max=3, zone_chunk=0,
                           memory_budget_mb=1.0)
    ex = MiningExecutor.from_config(cfg)
    assert ex._zone_chunk_for(4096, 1024) == 0
    # a budget alone (zone_chunk=None) still derives a chunk, silently
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        cfg2 = MiningConfig(delta=30, l_max=3, memory_budget_mb=1.0)
    assert MiningExecutor.from_config(cfg2)._zone_chunk_for(4096, 1024) > 0


# -- value semantics --------------------------------------------------------

def test_frozen_and_hashable():
    cfg = MiningConfig(delta=60, l_max=3)
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg.delta = 10
    assert cfg == MiningConfig(delta=60, l_max=3)
    assert hash(cfg) == hash(MiningConfig(delta=60, l_max=3))
    assert len({cfg, MiningConfig(delta=60, l_max=3)}) == 1


def test_with_updates_revalidates():
    cfg = MiningConfig(delta=60, l_max=3)
    assert cfg.with_updates(omega=4).omega == 4
    assert cfg.with_updates(omega=4) is not cfg
    with pytest.raises(ValueError, match="omega"):
        cfg.with_updates(omega=0)


def test_l_b_derived():
    assert MiningConfig(delta=60, l_max=3).l_b == 180


# -- serialization ----------------------------------------------------------

def test_json_round_trip_exact():
    cfg = MiningConfig(delta=45, l_max=4, omega=6, e_cap=128,
                       backend="numpy", zone_chunk=2, agg="hierarchical",
                       merge_cap=2048, allow_overflow=True)
    back = MiningConfig.from_json(cfg.to_json())
    assert back == cfg and hash(back) == hash(cfg)
    # dict form too
    assert MiningConfig.from_json(cfg.to_dict()) == cfg


def test_from_json_rejects_unknown_fields():
    with pytest.raises(ValueError, match="unknown MiningConfig field"):
        MiningConfig.from_json({"delta": 60, "l_max": 3, "typo_field": 1})


# -- CLI surface ------------------------------------------------------------

def test_cli_defaults_match_dataclass_defaults():
    ap = argparse.ArgumentParser()
    MiningConfig.add_cli_args(ap)
    assert MiningConfig.from_cli_args(ap.parse_args([])) == MiningConfig()


def test_cli_round_trip_non_defaults():
    ap = argparse.ArgumentParser()
    MiningConfig.add_cli_args(ap)
    args = ap.parse_args([
        "--delta", "45", "--l-max", "4", "--omega", "6", "--e-cap", "128",
        "--backend", "numpy", "--zone-chunk", "2", "--agg", "hierarchical",
        "--merge-cap", "512", "--allow-overflow",
    ])
    cfg = MiningConfig.from_cli_args(args)
    assert cfg == MiningConfig(
        delta=45, l_max=4, omega=6, e_cap=128, backend="numpy",
        zone_chunk=2, agg="hierarchical", merge_cap=512, allow_overflow=True)


def test_cli_rejects_bad_choices():
    ap = argparse.ArgumentParser()
    MiningConfig.add_cli_args(ap)
    with pytest.raises(SystemExit):
        ap.parse_args(["--agg", "bogus"])
    with pytest.raises(SystemExit):
        ap.parse_args(["--agg", "pipelined"])
    assert AGG_MODES == ("auto", "legacy", "hierarchical")


# -- removed shims ----------------------------------------------------------

def test_discover_shims_removed_with_engine_pointer():
    """The one-shot kwargs functions finished their deprecation cycle:
    still importable, but calling raises with migration instructions."""
    g = random_graph(3, 200, 20, 2_000)
    with pytest.raises(RuntimeError, match="PTMTEngine"):
        discover(g, delta=60, l_max=3, omega=4)
    with pytest.raises(RuntimeError, match="PTMTEngine"):
        discover_sequential(g, delta=60, l_max=3)
