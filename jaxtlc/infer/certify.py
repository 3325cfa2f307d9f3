"""Certification: are the filter's survivors actually invariants?

Two machine-checked bases, reported distinctly:

* **reachable-inductive** (exact evidence only): a device pass over the
  reachable set's one-step successors through the SpecBackend's own
  expand kernel - `Init => cand` over the initial vectors plus
  `cand /\\ Next => cand'` over every (reachable state, enabled
  successor) pair.  Over the EXACT reachable set this is precisely the
  induction that proves cand holds on every reachable state, i.e. a
  machine-certified invariant (it is induction over reachability, not
  a proof of inductiveness over the full type universe - the honest
  wording the driver emits).
* **absint**: the candidate is one of the bound atoms conjectured FROM
  a certified analysis.absint report - the narrowing fixpoint already
  machine-checked `Init ⊑ R` and `step#(R) ⊑ R` for its domains, so
  these candidates certify with no device pass at all (and remain
  certified even under sampled evidence).

Survivors with neither basis are reported honestly as "consistent with
evidence only".
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import numpy as np

CERT_BLOCK = 1024


class CertifyOutcome(NamedTuple):
    init_ok: np.ndarray  # [P] bool: Init => cand
    closed: np.ndarray  # [P] bool: cand /\ Next => cand' over evidence


def make_certify_fn(backend, inv_fns: list):
    """One jitted kernel: per evidence state, evaluate every candidate
    on the state AND on each enabled one-step successor (the backend's
    own expand step under vmap), returning the [P] escaped-bits of the
    block - True means some pre-state satisfying the candidate has an
    enabled successor that does not."""
    from ..engine.backend import require_unconstrained

    require_unconstrained(backend, "invariant inference (-infer)")

    import jax
    import jax.numpy as jnp

    step = backend.step

    def one(vec):  # [F] -> [P] escape bits for this state
        pre = jnp.stack([fn(vec[None])[0] for fn in inv_fns])  # [P]
        succs, valid, _action, _afail, _ovf = step(vec)
        post = jnp.stack([fn(succs) for fn in inv_fns])  # [P, L]
        return (pre[:, None] & valid[None, :] & ~post).any(axis=1)

    def f(fields):  # [B, F] -> [P]
        return jax.vmap(one)(fields).any(axis=0)

    return jax.jit(f)


def certify_closed(certify_fn, fields: np.ndarray, n_preds: int,
                   block: int = CERT_BLOCK) -> np.ndarray:
    """[P] closed-under-Next bits over the evidence set, dispatched in
    fixed blocks padded with replicas of the first real row (real
    states: a pad row can only duplicate an escape the evidence already
    contains, never fabricate one)."""
    n = fields.shape[0]
    escaped = np.zeros(n_preds, bool)
    for start in range(0, n, block):
        b = fields[start:start + block]
        real = b.shape[0]
        if real < block:
            b = np.concatenate(
                [b, np.repeat(b[:1], block - real, axis=0)], axis=0
            )
        escaped |= np.asarray(certify_fn(b))
    return ~escaped


def host_inductive_check(system, cand_ast, states: list) -> bool:
    """The host-oracle verification of the reachable-inductive claim:
    `Init => cand` and, for every evidence state satisfying cand,
    every successor satisfies cand too - `ev.eval` + the host
    successor enumerator, no device code (the test pin the acceptance
    bar names)."""
    ev = system.ev

    def holds(st) -> bool:
        env = dict(ev.constants)
        env.update(zip(system.variables, st))
        try:
            return ev.eval(cand_ast, env) is True
        except Exception:
            return False

    for st in system.initial_states():
        if not holds(st):
            return False
    for st in states:
        if not holds(st):
            continue
        for _label, nxt in system.successors(st):
            if not holds(nxt):
                return False
    return True
