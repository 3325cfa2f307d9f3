"""The per-bit, per-permutation form of the orbit canonicalization that
`engine.reduce` had until ISSUE 33 (one shift, and, shift, or a mask bit
a permutation; a running compare chain a permutation), kept here as the
tests' independent statement of what a field program means: the array
form that replaced it has to agree with it bit for bit."""

import numpy as np

from jaxtlc.struct.codec import MASK_BITS_PER_FIELD


def apply_program(prog, flat, xp=np) -> list:
    """One permutation program on flat [N, F]: the F per-field columns."""
    F = flat.shape[-1]
    cols = [flat[..., j] for j in range(F)]
    if prog.src is not None:
        cols = [cols[int(prog.src[j])] for j in range(F)]
    for field, tbl, guards in prog.tables:
        t = xp.asarray(tbl)
        nv = t[xp.clip(cols[field], 0, len(tbl) - 1)]
        if guards:
            cond = None
            for g in guards:
                c = (cols[g[1]] > g[2]) if g[0] == "len" \
                    else (cols[g[1]] != 0)
                cond = c if cond is None else (cond & c)
            nv = xp.where(cond, nv, cols[field])
        cols[field] = nv
    for off, widths, sigma in prog.masks:
        newf = [xp.zeros_like(cols[off]) for _ in widths]
        for i, d in enumerate(sigma):
            bit = (cols[off + i // MASK_BITS_PER_FIELD]
                   >> (i % MASK_BITS_PER_FIELD)) & 1
            fi, bo = d // MASK_BITS_PER_FIELD, d % MASK_BITS_PER_FIELD
            newf[fi] = newf[fi] | (bit << bo)
        for fi in range(len(widths)):
            cols[off + fi] = newf[fi]
    return cols


def canon(plan, flat, xp=np):
    """Running lexicographic minimum over the plan's programs."""
    F = flat.shape[-1]
    best = [flat[..., j] for j in range(F)]
    for prog in plan.programs:
        cand = apply_program(prog, flat, xp)
        lt = xp.zeros(flat.shape[:-1], bool)
        eq = xp.ones(flat.shape[:-1], bool)
        for j in range(F):
            lt = lt | (eq & (cand[j] < best[j]))
            eq = eq & (cand[j] == best[j])
        best = [xp.where(lt, c, b) for c, b in zip(cand, best)]
    return xp.stack(best, axis=-1)
