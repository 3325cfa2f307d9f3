"""What decides `correct`: every job of the window against the plain
reference's pins, by exact equality (the limit of every comparison is a
difference of 0), plus the facts of its run journal.

Copied in spirit from chip_smoke.py's `_journal_facts` (TPU named in
run_start, exact final counts, no degradation-ladder event); kept here so
that no later PR can move it.  A job is judged by what the caller got
back (`result`) AND by what the program journalled (`events`): the two
have to agree with the pins independently.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

# journal events that mean the supervisor left the plain device path
LADDER_EVENTS = ("regrow", "retry", "degrade", "spill")
COUNT_KEYS = ("generated", "distinct", "depth")


def job_findings(rec: dict, pins: dict, device_word: str = "tpu",
                 engines: Optional[List[str]] = None,
                 journal: bool = True) -> List[Tuple[str, str]]:
    """Why this job is not correct, as (kind, text) pairs; empty when it
    is.  `rec` carries `ok` (the caller got a verdict at all), `result`
    (verdict, counts, queue, action_generated as the caller received
    them) and `events` (the job's run journal)."""
    if not rec.get("ok", True):
        return [("no verdict", f"no verdict: {rec.get('why', 'unknown')}")]
    bad: List[Tuple[str, str]] = []
    res = rec.get("result") or {}
    if res.get("verdict") != "ok":
        bad.append(("verdict not ok",
                    f"verdict {res.get('verdict')!r}, want 'ok'"))
    if res.get("queue") != 0:
        bad.append(("queue not empty",
                    f"queue {res.get('queue')!r} at the verdict, want 0"))
    for k in COUNT_KEYS:
        if res.get(k) != pins[k]:
            bad.append((k, f"{k} {res.get(k)!r}, want {pins[k]}"))
    want_actions = pins.get("action_generated")
    if want_actions is not None:
        got = {k: int(v) for k, v in
               (res.get("action_generated") or {}).items() if int(v)}
        if got != want_actions:
            diff = sorted(k for k in set(got) | set(want_actions)
                          if got.get(k) != want_actions.get(k))
            bad.append(("action_generated",
                        f"per-action generated totals differ at {diff[:4]}"))
    if engines and res.get("engine") not in engines:
        bad.append(("wrong engine",
                    f"engine {res.get('engine')!r}, want one of {engines}"))
    if not journal:  # the entry writes none: the result is all there is
        return bad
    events = rec.get("events")
    start = next((e for e in events or [] if e.get("event") == "run_start"),
                 None)
    final = next((e for e in events or [] if e.get("event") == "final"),
                 None)
    if start is None or final is None:
        bad.append(("journal disagrees", "no run journal" if events is None
                    else "journal lacks run_start or final"))
        return bad
    if device_word not in str(start.get("device", "")).lower():
        bad.append(("device not named",
                    f"run_start device {start.get('device')!r} "
                    f"does not name a {device_word}"))
    jgot = tuple(final.get(k) for k in COUNT_KEYS)
    jwant = tuple(pins[k] for k in COUNT_KEYS)
    if (final.get("verdict") != "ok" or final.get("queue") != 0
            or jgot != jwant):
        bad.append(("journal disagrees",
                    f"journal final {final.get('verdict')} {jgot} queue "
                    f"{final.get('queue')}, want ok {jwant} queue 0"))
    ladder = [e["event"] for e in events if e.get("event") in LADDER_EVENTS]
    if ladder:
        bad.append(("ladder events",
                    f"left the plain device path: {sorted(set(ladder))}"))
    return bad


SUMMARY_KINDS = ("verdict not ok", "queue not empty", "device not named",
                 "journal disagrees", "ladder events", "no verdict",
                 "wrong engine")


def judge(records: List[dict], config: dict, compiles_in_window: int,
          device_word: str = "tpu") -> Dict:
    """The run's verdict.  Returns dict(correct, attempted, failed,
    lines): `lines` prints each number compared beside its limit."""
    pins = config["pins"]
    findings = [job_findings(r, pins, device_word, config.get("engines"),
                             config.get("journal", True))
                for r in records]
    for r, f in zip(records, findings):
        r["findings"] = [text for _, text in f]
    kinds = [{kind for kind, _ in f} for f in findings]
    failed = sum(1 for f in findings if f)
    n = len(records)
    lines = []
    for k in COUNT_KEYS:
        got = [(r.get("result") or {}).get(k) for r in records]
        worst = max((abs(g - pins[k]) if isinstance(g, int) else pins[k]
                     for g in got), default=0)
        lines.append(f"compare {k}: {n} jobs, want {pins[k]} "
                     f"(reference), worst difference {worst}, limit 0")
    if pins.get("action_generated") is not None:
        off = sum(1 for ks in kinds if "action_generated" in ks)
        lines.append(f"compare action_generated: {n} jobs, "
                     f"{len(pins['action_generated'])} actions each, "
                     f"{off} jobs differ, limit 0")
    for what in SUMMARY_KINDS:
        c = sum(1 for ks in kinds if what in ks)
        lines.append(f"compare {what}: {c} jobs, limit 0")
    lines.append(f"compare compiles in the window: {compiles_in_window}, "
                 "limit 0")
    if n == 0:
        lines.append("compare jobs completed: 0, at least 1")
    broken = [r for r in records if r["findings"]]
    lines += [f"job {r.get('index')} ({r.get('klass')}): "
              + "; ".join(r["findings"]) for r in broken[:10]]
    correct = n > 0 and failed == 0 and compiles_in_window == 0
    return dict(correct=correct, attempted=n, failed=failed, lines=lines)
