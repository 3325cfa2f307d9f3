"""Scaled-config (N reconcilers x M binders) differential tests.

The scaled generalization (VERDICT.md item 9; BASELINE.json "KubeAPI.tla
scaled") must be a conservative extension: the (1,1) instance is the same
action system as Model_1 up to renaming, so its state graph must be
isomorphic (identical counts); larger instances are validated oracle-vs-
device exactly like the base config.
"""

import pytest

from jaxtlc.config import make_scaled, scaled_config
from jaxtlc.engine.bfs import check
from jaxtlc.spec import oracle
from jaxtlc.spec.codec import get_codec


def test_scaled_1x1_isomorphic_to_model1_ff():
    # renaming (Client->Client0 etc.) cannot change the graph
    r = oracle.bfs(make_scaled(1, 1, False, False))
    assert (r.generated, r.distinct, r.depth) == (17020, 8203, 109)
    assert not r.violations


def test_scaled_2x0_initial_states():
    cfg = make_scaled(2, 0, False, False)
    inits = oracle.initial_states(cfg)
    assert len(inits) == 4  # 2^R, shouldReconcile in [reconcilers -> BOOLEAN]
    assert len(set(inits)) == 4


def test_scaled_2x0_ff_oracle_vs_device():
    cfg = make_scaled(2, 0, False, False)
    r = oracle.bfs(cfg)
    assert (r.generated, r.distinct, r.depth) == (6604, 3025, 61)
    assert not r.violations
    d = check(cfg, chunk=256, queue_capacity=1 << 12, fp_capacity=1 << 13)
    assert (d.generated, d.distinct, d.depth) == (6604, 3025, 61)
    assert d.violation == 0 and d.queue_left == 0


def test_scaled_codec_roundtrip_2x0():
    cfg = make_scaled(2, 0, False, False)
    cdc = get_codec(cfg)
    states = []
    oracle.bfs(cfg, on_level=lambda d, f: states.extend(f))
    for s in states:
        assert cdc.decode(cdc.encode(s)) == s
    encs = {tuple(map(int, cdc.encode(s))) for s in states}
    assert len(encs) == len(states)


@pytest.mark.slow
def test_scaled_2x0_tt_oracle_vs_device():
    cfg = make_scaled(2, 0, True, True)
    r = oracle.bfs(cfg)
    assert (r.generated, r.distinct, r.depth) == (156496, 42849, 67)
    assert not r.violations
    d = check(cfg, chunk=512, queue_capacity=1 << 14, fp_capacity=1 << 17)
    assert (d.generated, d.distinct, d.depth) == (156496, 42849, 67)
    assert d.violation == 0


@pytest.mark.slow
def test_scaled_1x2_ff_exact():
    """Two binders racing to bind the one PVC - the full Update/HasRead
    coupling only n_binders >= 2 exercises.  The 9.94M-state space is far
    past the Python oracle's reach (the r3 red test tried 3M and failed;
    VERDICT r3 item 3), so the pins come from cross-platform device-engine
    agreement - TPU v5e (chunk 16384 and independently at other chunk
    sizes) and CPU (chunk 16384) both measured 30,582,846 generated /
    9,942,722 distinct / depth 160 on 2026-07-30 (SCALED_VALIDATION.json
    records the runs).  ~6 min on this box's CPU core."""
    cfg = make_scaled(1, 2, False, False)
    d = check(cfg, chunk=16384, queue_capacity=1 << 19, fp_capacity=1 << 24)
    assert (d.generated, d.distinct, d.depth) == (30582846, 9942722, 160)
    assert d.violation == 0 and d.queue_left == 0


def test_scaled_pins_match_validation_artifact():
    """The benchmark's kubeapi-2x1ff pins and the slow tests cite
    SCALED_VALIDATION.json; the three sources must agree, and every
    recorded validation run must reproduce its pin exactly."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "SCALED_VALIDATION.json")) as f:
        doc = json.load(f)
    assert doc["pins"]["2x1FF"] == [62014325, 19359985, 186]
    assert doc["pins"]["1x2FF"] == [30582846, 9942722, 160]
    # the benchmark configuration's pins (read-only here: they come
    # from benchmark/reference/pin_digest.py) match the artifact pin
    with open(os.path.join(root, "benchmark", "configs",
                           "kubeapi-2x1ff.json")) as f:
        pins = json.load(f)["pins"]
    assert [pins["generated"], pins["distinct"],
            pins["depth"]] == doc["pins"]["2x1FF"]
    # recorded runs: exact agreement, and >= 2 independent geometries +
    # >= 2 platforms for the flagship family
    for run in doc["runs"]:
        pin = doc["pins"][run["workload"]]
        assert [run["generated"], run["distinct"], run["depth"]] == pin
    flagship = [r for r in doc["runs"] if r["workload"] == "2x1FF"]
    assert len({(r["chunk"], r["fp_capacity_log2"]) for r in flagship}) >= 3
    platforms = {r["platform"][:3] for r in doc["runs"]}
    assert len(platforms) >= 2  # TPU and CPU


def test_scaled_config_factory():
    cfg, kwargs = scaled_config()
    assert cfg.n_reconcilers == 2 and cfg.n_clients == 3
    assert kwargs["chunk"] > 0
