"""Device-resident state-space reduction (ISSUE 18).

Two sound prunings, both applied inside the expand stage so every
engine that goes through `bfs.make_stage_pair` (fused, pipelined,
spill, narrowed, covered, deferred, sharded) inherits them with zero
per-engine code:

* **Symmetry reduction** - canonicalize every successor to the
  lexicographically-least member of its orbit under the verified
  symmetric constant sets (analysis.symfind) BEFORE packing and
  fingerprinting, so the existing fpset dedups orbit representatives
  and the queue never carries two states equal up to a permutation of
  model values.  The canonicalization is a dense tournament over the
  codec's flat [N, F] int32 fields: each non-identity permutation of
  the symmetry group compiles to a static *field program* (gather +
  per-field remap tables + bitmask bit-permutations), the programs
  are stacked into arrays (`_ArrayForm`: one remap matrix a field, one
  bits-to-fields matrix a mask, for all programs at once), and the
  kernel takes the lexicographic minimum over the stacked images field
  by field - no sort, no host pass, no new engine loops, and a few
  equations a field whatever the group's order or a mask's width.

* **POR (singleton ample sets)** - when a state enables an action the
  static analysis proved independent-of-everything, invisible and
  cycle-safe (symfind.safe_por_actions), expand only that action's
  lanes: the pruned interleavings commute to the kept order without
  changing any invariant verdict.  The deadlock test runs on the
  pre-pruning mask, so pruning never fabricates or hides a deadlock.

Because a wrong permutation table would silently corrupt the dedup
(two encodings of one state, or two states folded together), symmetry
runs are self-certifying: every body re-canonicalizes a pseudorandomly
permuted image of one sampled canonical row and latches any mismatch
into a sticky verdict column (COL_SYM, the certified-bounds COL_CERT
pattern from analysis.absint).  ``JAXTLC_DEBUG_SYM_LIE=1`` corrupts
one remap table at plan build so the trip wire itself is testable.

Field-program correctness notes (the load-bearing invariants):

* Programs always apply to the ORIGINAL fields; the group property
  makes min over {pi(s) : pi in G} the orbit canonical form, so no
  composition of programs is ever needed.
* Canonical zeros stay zero: SeqNode slots past the length and absent
  optional RecNode children are zero-filled by the codec, so their
  remap tables are guarded (`where(len > k, ...)` / presence bit) -
  a mask bit-permutation needs no guard (it maps the empty set to the
  empty set).
* A permutation of record FIELD NAMES (a function over a symmetric
  domain that fell back to RecNode) moves whole field blocks; that is
  only realisable when the moved siblings share one layout object,
  otherwise the set is rejected at plan build.
"""

from __future__ import annotations

import itertools
import os
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..struct.codec import (
    MASK_BITS_PER_FIELD,
    EnumLeaf,
    MaskLeaf,
    RecNode,
    SeqNode,
)
from ..struct.eval import permute_value


class RejectSet(Exception):
    """A candidate symmetric set's permutation cannot be realised as a
    codec field program (permuted value outside an enumerated universe,
    unequal sibling layouts under permuted field names); the caller
    drops the set and reports why."""


class _PermProgram(NamedTuple):
    """One permutation as a static transform of the flat [N, F] fields:
    an optional whole-field gather (record field-name moves), per-field
    remap tables with canonical-zero guards, and per-mask bit
    permutations."""

    src: Optional[np.ndarray]  # [F] int32 dest<-src gather, None=identity
    tables: tuple  # ((field, np table, guards), ...) post-gather fields
    masks: tuple  # ((offset, widths, sigma), ...) bit i -> bit sigma[i]


def _enum_table(leaf: EnumLeaf,
                pmap: Dict[str, str]) -> Optional[np.ndarray]:
    tbl = np.arange(len(leaf.values), dtype=np.int32)
    changed = False
    for i, v in enumerate(leaf.values):
        pv = permute_value(v, pmap)
        if pv == v:
            continue
        j = leaf.index.get(pv)
        if j is None:
            raise RejectSet(
                f"permuted value {pv!r} falls outside the enumerated "
                "universe (shape not closed under the permutation)"
            )
        tbl[i] = j
        changed = True
    return tbl if changed else None


def _mask_sigma(leaf: MaskLeaf,
                pmap: Dict[str, str]) -> Optional[Tuple[int, ...]]:
    elem = leaf.elem
    sigma = list(range(leaf.n_bits))
    changed = False
    for i, v in enumerate(elem.values):
        pv = permute_value(v, pmap)
        if pv == v:
            continue
        j = elem.index.get(pv)
        if j is None:
            raise RejectSet(
                f"permuted set element {pv!r} outside the mask universe"
            )
        sigma[i] = j
        changed = True
    return tuple(sigma) if changed else None


def _emit(lay, off: int, pmap, prog: dict, guards: tuple) -> int:
    """Walk one layout at flat offset `off`, appending transform pieces
    for `pmap` to `prog`; returns the offset past the layout."""
    if isinstance(lay, EnumLeaf):
        tbl = _enum_table(lay, pmap)
        if tbl is not None:
            prog["tables"].append((off, tbl, guards))
        return off + 1
    if isinstance(lay, MaskLeaf):
        sigma = _mask_sigma(lay, pmap)
        if sigma is not None:
            prog["masks"].append((off, tuple(lay.widths), sigma))
        return off + lay.n_fields
    if isinstance(lay, SeqNode):
        tbl = _enum_table(lay.elem, pmap)
        if tbl is not None:
            for k in range(lay.cap):
                # padding slots past the length are canonical zeros
                prog["tables"].append(
                    (off + 1 + k, tbl, guards + (("len", off, k),))
                )
        return off + lay.n_fields
    if isinstance(lay, RecNode):
        spans = []  # (name, opt, child, start offset incl presence bit)
        o = off
        for name, opt, child in lay.entries:
            spans.append((name, opt, child, o))
            o += (1 if opt else 0) + child.n_fields
        by_name = {name: (opt, child, s) for name, opt, child, s in spans}
        for name, opt, child, start in spans:
            dst = pmap.get(name, name)
            if dst != name:
                # function over a symmetric domain in RecNode fallback:
                # move the whole field block entry `name` -> entry `dst`
                if dst not in by_name:
                    raise RejectSet(
                        f"record field {dst} missing (domain not "
                        "closed under the permutation)"
                    )
                d_opt, d_child, d_start = by_name[dst]
                if d_opt != opt or d_child is not child:
                    raise RejectSet(
                        f"record fields {name}/{dst} have different "
                        "layouts; block move not realisable"
                    )
                n = (1 if opt else 0) + child.n_fields
                for t in range(n):
                    prog["src"][d_start + t] = start + t
        for name, opt, child, start in spans:
            # recurse at the DESTINATION span: after the gather these
            # fields hold the source entry's codes, and content remaps
            # (atoms inside the child) apply post-gather
            g = guards + ((("opt", start),) if opt else ())
            o2 = _emit(child, start + (1 if opt else 0), pmap, prog, g)
            assert o2 == start + (1 if opt else 0) + child.n_fields
        return o
    raise RejectSet(f"no field program for layout {type(lay).__name__}")


# a remap table at most this long is applied as a one-hot product (the
# form the chip's matrix unit runs; an element gather costs it ~8 ns a
# row, PERF.md section 5), a longer one as a gather: a one-hot of an
# enumerated universe of 10^5 values would not fit beside the rows
ONEHOT_MAX = 1024
_BIG = np.int32(np.iinfo(np.int32).max)


class _ArrayForm(NamedTuple):
    """Every non-identity program of a plan, stacked: what `_images`
    applies to flat [N, F] in a handful of array operations."""

    n_programs: int
    src: Optional[np.ndarray]  # [Pm, F] dest<-src gather, None = none moves
    tables: tuple  # ((field, T [Pm, len] int32, guards), ...)
    masks: tuple  # ((offset, n_fields, W [Pm, n_fields*16, n_fields]), ...)
    touched: tuple  # fields some program changes, ascending


def _array_form(programs: List[_PermProgram], n_fields: int) -> _ArrayForm:
    Pm = len(programs)
    ident = np.arange(n_fields, dtype=np.int32)
    src = None
    if any(p.src is not None for p in programs):
        src = np.stack([ident if p.src is None else p.src
                        for p in programs])
    by_field: Dict[int, list] = {}
    for k, p in enumerate(programs):
        for field, tbl, guards in p.tables:
            ent = by_field.setdefault(field, [len(tbl), guards, {}])
            assert ent[0] == len(tbl) and ent[1] == guards
            ent[2][k] = tbl
    tables = tuple(
        (field, np.stack([rows.get(k, np.arange(n, dtype=np.int32))
                          for k in range(Pm)]), guards)
        for field, (n, guards, rows) in sorted(by_field.items())
    )
    by_mask: Dict[int, list] = {}
    for k, p in enumerate(programs):
        for off, widths, sigma in p.masks:
            by_mask.setdefault(off, [len(widths), {}])[1][k] = sigma
    masks = []
    B = MASK_BITS_PER_FIELD
    for off, (nf, sigmas) in sorted(by_mask.items()):
        # image field = sum over source bits of bit x 2^(its place in
        # the image field): one 0 / power-of-two matrix for all programs
        W = np.zeros((Pm, nf * B, nf), np.int32)
        for k in range(Pm):
            sigma = sigmas.get(k)
            for i in range(nf * B):
                d = sigma[i] if sigma is not None and i < len(sigma) else i
                W[k, i, d // B] = 1 << (d % B)
        masks.append((off, nf, W))
    touched = {f for f, _, _ in tables}
    for off, nf, _ in masks:
        touched |= set(range(off, off + nf))
    if src is not None:
        touched |= {j for j in range(n_fields) if (src[:, j] != j).any()}
    return _ArrayForm(Pm, src, tables, tuple(masks),
                      tuple(sorted(touched)))


def _product(spec: str, a, w, xp):
    """einsum `spec` of rows `a` with the static matrix `w`, exact: small
    non-negative integers.  On the device as bf16 into f32 (every entry
    an integer below 2^8 or a power of two, every sum below 2^24: exact
    on the matrix unit)."""
    if xp is np:
        return np.einsum(spec, a.astype(np.int64),
                         w.astype(np.int64)).astype(np.int32)
    return jnp.einsum(
        spec, a.astype(jnp.bfloat16), jnp.asarray(w, jnp.bfloat16),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)


def _images(form: _ArrayForm, flat, xp) -> Dict[int, object]:
    """The touched fields of every program's image of flat [N, F]:
    {field: [Pm, N]}."""
    Pm, N = form.n_programs, flat.shape[0]
    if form.src is not None:
        moved = flat[:, xp.asarray(form.src)]  # [N, Pm, F]

        def col(j):
            return moved[:, :, j].T
    else:
        def col(j):
            return xp.broadcast_to(flat[:, j][None, :], (Pm, N))
    out: Dict[int, object] = {}
    for field, T, guards in form.tables:
        n = T.shape[1]
        cur = col(field)
        if n <= ONEHOT_MAX and form.src is None:
            onehot = flat[:, field][:, None] == xp.arange(n)[None, :]
            both = _product("nk,km->mn", onehot, np.concatenate(
                [T.T & 0xFF, T.T >> 8], axis=1), xp)  # [2 Pm, N]
            nv = both[:Pm] + (both[Pm:] << 8)
            # a code outside the table (a trapped lane) stays as it is
            nv = xp.where((cur >= 0) & (cur < n), nv, cur)
        else:
            nv = xp.take_along_axis(xp.asarray(T), xp.clip(cur, 0, n - 1),
                                    axis=1)
        for g in guards:
            # canonical zeros stay zero (module docstring); the guard
            # columns are lengths and presence bits, which no table maps
            cond = (col(g[1]) > g[2]) if g[0] == "len" \
                else (col(g[1]) != 0)
            nv = xp.where(cond, nv, cur)
        out[field] = nv
    B = MASK_BITS_PER_FIELD
    for off, nf, W in form.masks:
        # image field = sum over source bits of bit x 2^(its place)
        if form.src is None:
            bits = (flat[:, off:off + nf, None] >> xp.arange(B)) & 1
            img = _product("nb,pbf->pfn", bits.reshape(N, nf * B), W, xp)
        else:  # each program permutes the bits of its own moved fields
            bits = (moved[:, :, off:off + nf, None] >> xp.arange(B)) & 1
            img = _product("npb,pbf->pfn",
                           bits.reshape(N, Pm, nf * B), W, xp)
        for fi in range(nf):
            out[off + fi] = img[:, fi, :]
    for j in form.touched:
        if j not in out:
            out[j] = col(j)
    return out


def _image_rows(form: _ArrayForm, flat, xp):
    """[Pm, N, F]: every program's image of flat's rows, whole."""
    img = _images(form, flat, xp)
    shape = (form.n_programs, flat.shape[0])
    return xp.stack([
        img[j] if j in img else xp.broadcast_to(flat[:, j][None, :], shape)
        for j in range(flat.shape[1])
    ], axis=-1)


def _lexmin(flat, images, xp):
    """The lexicographic minimum of flat's rows and their images, field
    by field: the images still level with the minimum so far compete
    for the next field, whose least value IS the minimum's field."""
    alive = None
    cols = []
    for j in range(flat.shape[1]):
        own = flat[:, j]
        if j not in images:
            cols.append(own)  # the same in every image
            continue
        col = xp.concatenate([own[None, :], images[j]], axis=0)  # [P, N]
        m = (col if alive is None else xp.where(alive, col, _BIG)).min(
            axis=0)
        level = col == m[None, :]
        alive = level if alive is None else alive & level
        cols.append(m)
    return xp.stack(cols, axis=-1)


class ReducePlan:
    """Compiled symmetry group over one codec: a field program per
    non-identity permutation plus the tournament canonicalizer."""

    def __init__(self, cdc, sym_sets: Dict[str, Tuple[str, ...]],
                 lie: Optional[bool] = None):
        self.cdc = cdc
        self.sym_sets = {k: tuple(v) for k, v in sym_sets.items()}
        bases = [tuple(sorted(a)) for a in self.sym_sets.values()]
        pmaps: List[Dict[str, str]] = []
        for combo in itertools.product(
                *[list(itertools.permutations(b)) for b in bases]):
            pmap = {}
            for base, perm in zip(bases, combo):
                pmap.update(
                    {a: p for a, p in zip(base, perm) if a != p}
                )
            if pmap:
                pmaps.append(pmap)
        self.n_perms = len(pmaps) + 1  # group order incl identity
        self.programs = [self._build(p) for p in pmaps]
        if lie is None:
            lie = os.environ.get("JAXTLC_DEBUG_SYM_LIE", "") == "1"
        if lie:
            self._inject_lie()
        self.form = _array_form(self.programs, cdc.n_fields)

    def _build(self, pmap: Dict[str, str]) -> _PermProgram:
        prog = {
            "src": np.arange(self.cdc.n_fields, dtype=np.int32),
            "tables": [],
            "masks": [],
        }
        off = 0
        for lay in self.cdc.layouts:
            off = _emit(lay, off, pmap, prog, ())
        assert off == self.cdc.n_fields
        moved = not np.array_equal(
            prog["src"], np.arange(self.cdc.n_fields, dtype=np.int32)
        )
        return _PermProgram(
            src=prog["src"] if moved else None,
            tables=tuple(prog["tables"]),
            masks=tuple(prog["masks"]),
        )

    def _inject_lie(self) -> None:
        """Debug hook: swap two entries of the first remap table so the
        plan is no longer a group action - the orbit-check column must
        trip (tests/test_reduce.py pins exit 1)."""
        for i, p in enumerate(self.programs):
            for j, (field, tbl, guards) in enumerate(p.tables):
                if len(tbl) >= 2:
                    bad = tbl.copy()
                    bad[[0, 1]] = bad[[1, 0]]
                    tables = list(p.tables)
                    tables[j] = (field, bad, guards)
                    self.programs[i] = p._replace(tables=tuple(tables))
                    return

    # -- canonicalization --------------------------------------------------

    def _canon(self, flat, xp):
        return _lexmin(flat, _images(self.form, flat, xp), xp)

    def canon(self, flat):
        """Orbit-canonical form of flat [N, F] int32 on device: the
        lexicographic minimum over every group element applied to the
        ORIGINAL fields (group property - no composition needed)."""
        if not self.programs:
            return flat
        with jax.named_scope("jaxtlc.canon"):
            return self._canon(flat, jnp)

    def canon_host(self, flat: np.ndarray) -> np.ndarray:
        """Numpy twin of `canon` - seeds the initial frontier and backs
        the oracle tests."""
        arr = np.asarray(flat, np.int32)
        if not self.programs:
            return arr
        return np.asarray(self._canon(arr, np), np.int32)

    def images_host(self, flat: np.ndarray) -> np.ndarray:
        """[Pm, N, F]: every non-identity program's image of flat's
        rows, on the host (the oracle tests' orbit enumeration)."""
        return _image_rows(self.form, np.asarray(flat, np.int32), np)

    # -- runtime orbit certification ---------------------------------------

    def orbit_check(self, flat, fvalid):
        """Sticky-column sample: take one valid canonical row, apply
        EVERY group element to it, re-canonicalize each variant, and
        flag any mismatch - if the programs are a true group action
        the canonical form is orbit-invariant, so a trip means the
        plan (or the kernel under it) is lying.  Checking the whole
        orbit of the sample (P images of one row, each canonicalized
        again, P <= PERM_LIMIT) rather than one element keeps the
        certificate sharp: a corrupted table that touches only a few
        codes still trips the first time the sample's orbit crosses
        them.  Returns a bool scalar."""
        if not self.programs:
            return jnp.zeros((), bool)
        with jax.named_scope("jaxtlc.canon"):
            row = flat[jnp.argmax(fvalid)][None, :]  # [1, F]
            variants = _image_rows(self.form, row, jnp)[:, 0, :]  # [Pm, F]
            recanon = self._canon(variants, jnp)
            ok = (recanon == row).all()
        return fvalid.any() & ~ok


def build_plan(cdc, sym_sets: Dict[str, Tuple[str, ...]]) -> Tuple[
        Optional["ReducePlan"], Dict[str, str]]:
    """Build a ReducePlan over `cdc` for the statically-verified sets,
    dropping (with reasons) any set whose permutations cannot be
    realised as field programs.  Greedy per-set so one unrealisable
    set does not lose the others."""
    kept: Dict[str, Tuple[str, ...]] = {}
    dropped: Dict[str, str] = {}
    for name, atoms in sym_sets.items():
        try:
            ReducePlan(cdc, {name: atoms}, lie=False)
        except RejectSet as e:
            dropped[name] = str(e)
            continue
        kept[name] = tuple(atoms)
    if not kept:
        return None, dropped
    return ReducePlan(cdc, kept), dropped


# ---------------------------------------------------------------------------
# POR expand-time mask
# ---------------------------------------------------------------------------


def por_keep(valid, lane_action, safe_vec, n_labels: int):
    """Singleton-ample pruning of one popped block: valid [B, L] bool,
    lane_action [L] int32 (static lane -> action id), safe_vec
    [n_labels] bool.  Where a safe action is enabled, keep only the
    lanes of the LOWEST-id safe enabled action (all its bindings - the
    ample set is the whole action); otherwise keep everything."""
    ids = jnp.arange(n_labels, dtype=jnp.int32)
    onehot = lane_action[:, None] == ids[None, :]  # [L, A]
    enabled = (valid[:, :, None] & onehot[None, :, :]).any(axis=1)
    safe_enabled = enabled & safe_vec[None, :]
    has_safe = safe_enabled.any(axis=1)
    chosen = jnp.min(
        jnp.where(safe_enabled, ids[None, :], jnp.int32(n_labels)),
        axis=1,
    )
    lane_keep = lane_action[None, :] == chosen[:, None]  # [B, L]
    return jnp.where(has_safe[:, None], valid & lane_keep, valid)


class ReduceOps(NamedTuple):
    """The reduction capability a backend hands the expand stage:
    `plan` canonicalizes successors (None = symmetry off), `safe_ids`
    are the action ids POR may use as singleton ample sets (empty = POR
    off), `sym_sets`/`dropped_sets` feed journal + report plumbing."""

    plan: object = None  # ReducePlan or None
    safe_ids: Tuple[int, ...] = ()
    por: bool = False
    sym_sets: Tuple[Tuple[str, Tuple[str, ...]], ...] = ()
    dropped_sets: Tuple[Tuple[str, str], ...] = ()

    @property
    def orbit_factor(self) -> int:
        return self.plan.n_perms if self.plan is not None else 1
