"""Struct specs under the SHARED resil supervisor (ISSUE 3): the
LaneCompiler step is a first-class engine kernel, so checkpoint ->
SIGTERM -> -recover resume and undersized-fpset auto-regrow run through
exactly the recovery code the hand kernel uses - no struct-specific
paths - and every recovered run is pinned bit-for-bit against the clean
run (mirroring tests/test_resil.py's hand-kernel cases).  Plus the
step-compile cache: in-process memoization of the parse -> shape-infer
-> lane-compile pipeline (the persistent XLA cache is a process-entry
concern: tests/test_runtime.py, tests/test_cli.py).
"""

import pytest

from jaxtlc.engine import checkpoint as ck
from jaxtlc.resil import FaultPlan, SupervisorOptions, check_supervised
from jaxtlc.struct import cache
from jaxtlc.struct.backend import struct_meta_config
from jaxtlc.struct.engine import check_struct
from jaxtlc.struct.loader import load

CFG = "specs/TwoPhase.toolbox/Model_1/MC.cfg"
EXPECT = (114, 56, 8)
KW = dict(chunk=16, queue_capacity=1 << 8)


def signature(r):
    """Full exactness signature of a CheckResult."""
    return (r.generated, r.distinct, r.depth, r.violation,
            tuple(sorted(r.action_generated.items())),
            tuple(sorted(r.action_distinct.items())),
            r.outdegree)


@pytest.fixture(scope="module")
def model():
    return load(CFG)


@pytest.fixture(scope="module")
def clean(model):
    r = check_struct(model, fp_capacity=1 << 10, check_deadlock=False,
                     **KW)
    assert (r.generated, r.distinct, r.depth) == EXPECT
    return r


def _supervised(model, opts, fp_capacity=1 << 10):
    return check_supervised(
        None, fp_capacity=fp_capacity,
        backend=cache.get_backend(model, check_deadlock=False),
        meta_config=struct_meta_config(model), check_deadlock=False,
        opts=opts, **KW,
    )


def test_struct_regrow_undersized_matches_clean(model, clean):
    # fp 2^7 cannot hold 56 distinct under the ncand-pessimistic
    # highwater trigger: the supervisor must double its way out and
    # still match the correctly-sized fused run on EVERY statistic
    sr = _supervised(model, SupervisorOptions(ckpt_every=2),
                     fp_capacity=1 << 7)
    assert sr.regrows >= 1 and not sr.interrupted
    assert sr.params["fp_capacity"] > (1 << 7)
    assert signature(sr.result) == signature(clean)


def test_struct_sigterm_resume_exact(tmp_path, model, clean):
    p = str(tmp_path / "ck.npz")
    events = []
    sr = _supervised(
        model,
        SupervisorOptions(
            ckpt_path=p, ckpt_every=1,
            faults=FaultPlan.parse("sigterm@1"),
            on_event=lambda k, i: events.append(k),
        ),
    )
    assert sr.interrupted and "interrupted" in events
    assert sr.result.queue_left > 0  # genuinely unfinished
    gens = ck.list_generations(p)
    assert gens, "drain must leave checkpoint generations"
    meta = ck.read_checkpoint_meta(gens[-1][1])
    # the checkpoint records WHICH spec it snapshots (digest + constants)
    assert meta["config"]["frontend"] == "struct"
    assert meta["config"]["digest"] == model.source_digest

    events2 = []
    sr2 = _supervised(
        model,
        SupervisorOptions(ckpt_path=p, ckpt_every=4, resume=True,
                          on_event=lambda k, i: events2.append(k)),
    )
    assert "recovery" in events2
    assert not sr2.interrupted
    assert signature(sr2.result) == signature(clean)


def test_struct_resume_rejects_other_spec(tmp_path, model):
    """A struct checkpoint must never resume under a different module
    text: the digest in the meta is a FIXED key.  Even a comment-only
    edit changes the digest - resumability is decided by text identity,
    not by whatever the engine would happen to compile."""
    p = str(tmp_path / "ck.npz")
    sr = _supervised(
        model,
        SupervisorOptions(ckpt_path=p, ckpt_every=1,
                          faults=FaultPlan.parse("sigterm@1")),
    )
    assert sr.interrupted
    d = tmp_path / "edited"
    d.mkdir()
    src = open("specs/TwoPhase.toolbox/Model_1/TwoPhase.tla").read()
    (d / "TwoPhase.tla").write_text(src + "\n\\* edited\n")
    (d / "MC.cfg").write_text(open(CFG).read())
    other = load(str(d / "MC.cfg"))
    assert other.source_digest != model.source_digest
    with pytest.raises(ValueError, match="config mismatch"):
        _supervised(
            other,
            SupervisorOptions(ckpt_path=p, ckpt_every=4, resume=True),
        )


def test_cli_struct_coverage_in_module_order(capsys):
    """-coverage for struct specs (previously rejected): the per-action
    distinct:generated lines render from the engine's act_gen/act_dist
    counters in module-definition (MC.out) order."""
    from jaxtlc.cli import main as cli_main

    rc = cli_main(["check", CFG, "-workers", "cpu", "-nodeadlock",
                   "-noTool", "-chunk", "16", "-qcap", "256",
                   "-fpcap", "1024", "-coverage"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "114 states generated, 56 distinct states found" in out
    positions = [
        out.index(f"<{a} of module TwoPhase>")
        for a in ("Vote", "Renege", "Collect", "Decide", "CallOff",
                  "ObeyCommit", "ObeyAbort")
    ]
    assert positions == sorted(positions), "not in module order"
    assert "<Vote of module TwoPhase>: 5:20" in out


# ---- step-compile cache --------------------------------------------------


def test_source_digest_stable_and_override_sensitive(model):
    assert model.source_digest and len(model.source_digest) == 64
    again = load(CFG)
    assert again.source_digest == model.source_digest


def test_engine_memo_returns_same_engine(model):
    geometry = dict(chunk=16, queue_capacity=1 << 8,
                    fp_capacity=1 << 10, fp_index=0, seed=0,
                    fp_highwater=0.85, check_deadlock=False)
    e1 = cache.get_engine(model, **geometry)
    e2 = cache.get_engine(model, **geometry)
    assert e1 is e2  # jit cache stays warm: same closures, no retrace
    # a different geometry is a different engine
    e3 = cache.get_engine(model, **{**geometry, "fp_capacity": 1 << 11})
    assert e3 is not e1
    # and a reloaded model with the same digest hits the same memo
    e4 = cache.get_engine(load(CFG), **geometry)
    assert e4 is e1
