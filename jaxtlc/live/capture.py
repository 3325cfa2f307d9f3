"""On-device edge capture: the successor relation as index tensors.

Pass 1 (engine.bfs.make_enumerator) leaves the reachable set on device as
an append-only packed-state array whose row index is the state id.  This
module runs pass 2: every state is re-expanded through the same vmapped
kernel, each successor's id is resolved by a batched binary search over
the fingerprint-sorted state array (the tensor-core-BFS trick: the edge
relation never exists as host objects, only as index tensors), and the
deduplicated relation is emitted as (src, dst, action, state_changing)
int32 chunks.

Memory tiering: each sweep dispatch fills a fixed-capacity device chunk
(chunk * n_lanes edges); the host side accumulates drained chunks and -
when `spill_path` is set and the RAM budget is exceeded - spills them as
sequential .npz part files with the checkpoint tier's atomic
tmp-file + rename discipline (engine.checkpoint.save_checkpoint), so
multi-hundred-million-edge captures are disk-bounded like TLC's
DiskFPSet, not RAM-bounded.

Exactness: id resolution is fingerprint-based, so two distinct states
colliding on one 64-bit fingerprint would merge - exactly the risk class
the exhaustive engine already accepts and reports (MC.out:39-42); a
successor whose fingerprint is NOT in the enumerated set halts loudly
(it would mean the two passes disagree - a checker bug, never silent).
"""

from __future__ import annotations

import os
import zlib
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..engine.bfs import OK, VIOLATION_NAMES, make_enumerator
from ..engine.fingerprint import DEFAULT_FP_INDEX, DEFAULT_SEED, fp64_words_mxu


class CapturedGraph(NamedTuple):
    """The device-captured reachable graph; ids are enumerator rows."""

    n_states: int
    init_count: int  # ids 0..init_count-1 are the initial states
    states: np.ndarray  # [V, W] uint32 packed states, id = row
    src: np.ndarray  # [E] int32
    dst: np.ndarray  # [E] int32
    action: np.ndarray  # [E] int32 action label id (backend.labels index)
    changed: np.ndarray  # [E] bool: state-changing edge (src != dst)


class _EdgeSpill:
    """Fixed-capacity host tier for drained edge chunks.

    Holds [n, 4] int32 blocks in RAM up to `ram_edges`; past that (and
    only when a spill path is given) full blocks are written as
    sequential .npz part files using the checkpoint tier's atomic
    tmp + rename discipline, and re-read once at finalize."""

    def __init__(self, spill_path: Optional[str] = None,
                 ram_edges: int = 1 << 26):
        self.spill_path = spill_path
        self.ram_edges = ram_edges
        self.blocks: List[np.ndarray] = []
        self.in_ram = 0
        self.parts: List[str] = []

    def append(self, block: np.ndarray) -> None:
        if not len(block):
            return
        self.blocks.append(block)
        self.in_ram += len(block)
        if self.spill_path is not None and self.in_ram > self.ram_edges:
            self._spill()

    def _spill(self) -> None:
        from ..engine.checkpoint import fsync_replace

        part = f"{self.spill_path}.edges{len(self.parts):05d}.npz"
        tmp = part + ".tmp"
        edges = np.concatenate(self.blocks)
        crc = np.uint32(zlib.crc32(np.ascontiguousarray(edges).tobytes()))
        with open(tmp, "wb") as f:
            np.savez_compressed(f, edges=edges, crc=crc)
            # fsync BEFORE the rename: os.replace alone orders only the
            # metadata, so a crash could publish a part file whose bytes
            # never hit the platter - recovered captures would then read
            # a torn edge relation
            fsync_replace(tmp, part, f=f)
        self.parts.append(part)
        self.blocks = []
        self.in_ram = 0

    def finalize(self) -> np.ndarray:
        loaded = []
        for part in self.parts:
            with np.load(part) as z:
                edges = z["edges"]
                if "crc" in z.files and zlib.crc32(
                    np.ascontiguousarray(edges).tobytes()
                ) != int(z["crc"]):
                    raise IOError(
                        f"edge-spill part {part!r} failed CRC verification "
                        "- torn write or bit rot; re-run the capture"
                    )
                loaded.append(edges)
            os.remove(part)
        if self.blocks:
            loaded.append(np.concatenate(self.blocks))
        if not loaded:
            return np.zeros((0, 4), np.int32)
        return np.concatenate(loaded)


def _pair_searchsorted(s_hi, s_lo, q_hi, q_lo, n: int):
    """Vectorized lower-bound binary search over (hi, lo) sorted pairs.

    jax has no uint64, so the 64-bit fingerprint stays as two uint32
    planes and the comparator is lexicographic; the static log2(n)
    unrolled rounds are each one gather."""
    lo_i = jnp.zeros(q_hi.shape, jnp.int32)
    hi_i = jnp.full(q_hi.shape, n, jnp.int32)
    for _ in range(max(1, (n - 1).bit_length())):
        cont = lo_i < hi_i
        mid = (lo_i + hi_i) >> 1
        m_hi = s_hi[jnp.minimum(mid, n - 1)]
        m_lo = s_lo[jnp.minimum(mid, n - 1)]
        less = (m_hi < q_hi) | ((m_hi == q_hi) & (m_lo < q_lo))
        lo_i = jnp.where(cont & less, mid + 1, lo_i)
        hi_i = jnp.where(cont & ~less, mid, hi_i)
    return lo_i


def capture_edges(
    backend,
    chunk: int = 1024,
    state_capacity: int = 1 << 20,
    fp_capacity: int = 1 << 20,
    fp_index: int = DEFAULT_FP_INDEX,
    seed: int = DEFAULT_SEED,
    spill_path: Optional[str] = None,
    ram_edges: int = 1 << 26,
) -> CapturedGraph:
    """Enumerate the reachable set and capture its edge relation.

    `backend` is an engine.sharded.SpecBackend (kubeapi_backend or
    gen_backend), so any spec the sharded engine can run gets its graph
    captured with zero per-state host work.
    """
    from ..engine.backend import require_unconstrained

    require_unconstrained(backend, "the liveness graph capture (PROPERTY / -liveness)")

    cdc = backend.cdc
    F = cdc.n_fields
    W = (cdc.nbits + 31) // 32
    L = backend.n_lanes
    nbits = cdc.nbits
    ncand = chunk * L
    init_count = int(np.asarray(backend.initial_vectors()).shape[0])

    # ---- pass 1: fused enumeration (ids = append order) ----
    init_fn, run_fn = make_enumerator(
        backend, chunk=chunk, state_capacity=state_capacity,
        fp_capacity=fp_capacity, fp_index=fp_index, seed=seed,
    )
    carry = jax.block_until_ready(run_fn(init_fn()))
    code = int(carry.viol)
    if code != OK:
        raise RuntimeError(
            f"liveness enumeration halted: {VIOLATION_NAMES[code]}"
        )
    V = int(carry.tail)
    states_np = np.asarray(carry.states)[:V]
    del carry
    states = jnp.asarray(states_np)

    # ---- fingerprint-sorted id map ----
    lo, hi = fp64_words_mxu(states, nbits, fp_index, seed)
    s_hi, s_lo, perm = lax.sort(
        (hi, lo, jnp.arange(V, dtype=jnp.int32)), num_keys=2
    )

    # states padded to a whole number of sweep chunks
    Vp = -(-V // chunk) * chunk
    states_pad = jnp.zeros((Vp, W), jnp.uint32).at[:V].set(states)
    step = backend.step

    @jax.jit
    def sweep(offset):
        block = lax.dynamic_slice(
            states_pad, (offset, jnp.int32(0)), (chunk, W)
        )
        batch = cdc.unpack(block)
        succs, valid, action, _afail, _ovf = jax.vmap(step)(batch)
        rows = jnp.arange(chunk, dtype=jnp.int32)
        valid = valid & ((offset + rows) < V)[:, None]
        flat = succs.reshape(ncand, F)
        fvalid = valid.reshape(-1)
        faction = jnp.broadcast_to(action, (chunk, L)).reshape(-1)
        packed = cdc.pack(flat)
        q_lo, q_hi = fp64_words_mxu(packed, nbits, fp_index, seed)
        idx = _pair_searchsorted(s_hi, s_lo, q_hi, q_lo, V)
        idx_c = jnp.minimum(idx, V - 1)
        found = (s_hi[idx_c] == q_hi) & (s_lo[idx_c] == q_lo) & (idx < V)
        dst = perm[idx_c]
        srcf = offset + jnp.arange(ncand, dtype=jnp.int32) // L
        changed = dst != srcf
        missing = (fvalid & ~found).any()
        # compact the valid edges to the front: one fixed-capacity chunk
        # of (src, dst, action, changed) per dispatch
        _, order = lax.sort(
            ((~fvalid).astype(jnp.uint32),
             jnp.arange(ncand, dtype=jnp.uint32)),
            num_keys=1, is_stable=True,
        )
        edges = jnp.stack(
            [srcf, dst, faction.astype(jnp.int32),
             changed.astype(jnp.int32)], axis=1,
        )[order]
        return edges, fvalid.sum(), missing

    spillway = _EdgeSpill(spill_path, ram_edges=ram_edges)
    for off in range(0, Vp, chunk):
        edges, nv, missing = sweep(jnp.int32(off))
        if bool(missing):
            raise RuntimeError(
                "edge capture found a successor outside the enumerated "
                "set (enumeration/capture disagree - checker bug)"
            )
        spillway.append(np.asarray(edges[: int(nv)]))
    raw = spillway.finalize()

    # dedup parallel (src, dst, action) triples; `changed` is determined
    # by (src, dst), so it survives dedup unchanged
    if len(raw):
        n_act = int(raw[:, 2].max()) + 1
        key = (
            raw[:, 0].astype(np.int64) * V + raw[:, 1].astype(np.int64)
        ) * n_act + raw[:, 2].astype(np.int64)
        _, uniq = np.unique(key, return_index=True)
        raw = raw[np.sort(uniq)]
    return CapturedGraph(
        n_states=V,
        init_count=init_count,
        states=states_np,
        src=raw[:, 0].astype(np.int32),
        dst=raw[:, 1].astype(np.int32),
        action=raw[:, 2].astype(np.int32),
        changed=raw[:, 3].astype(bool),
    )


# ---------------------------------------------------------------------------
# The relation kept on the device (struct route, ISSUE 41)
# ---------------------------------------------------------------------------

# a packed state of at most this many bits is its own index into a
# direct id table (2^bits int32: 256 MB at 26): one element gather a
# successor where the sorted-fingerprint search is 2 log2(V) of them
DIRECT_ID_BITS = 26


class DeviceGraph(NamedTuple):
    """What the capture program leaves on the device: the STATE-CHANGING
    successor rows in source order as a CSR (a self-loop carries nothing
    the analysis reads: `a_k` needs a changed step, and a state reaches
    itself), and the counts of everything it walked."""

    dst: jnp.ndarray  # [E + slack] int32: row e's destination id
    act: jnp.ndarray  # [E + slack] int8: its action label id
    row_start: jnp.ndarray  # [V + 1] int32: state s's rows are
    #                         row_start[s] .. row_start[s + 1]
    n_rows: jnp.ndarray  # int32: every successor row walked (= E)
    n_changed: jnp.ndarray  # int32: rows stored (src != dst)
    missing: jnp.ndarray  # bool: a successor outside the enumerated set


def make_device_capture(backend, chunk: int, n_states: int, n_edges: int,
                        fp_index: int = DEFAULT_FP_INDEX,
                        seed: int = DEFAULT_SEED):
    """(init_fn, program) of the capture over an enumerated state array,
    for `runtime.aot_build`: the program takes the enumerator's
    `states` ([n_states + A, W], id = row) and gives a DeviceGraph.

    `n_states` and `n_edges` are what the safety run counted (distinct;
    generated less the initial states): the stores are sized from them,
    nothing regrows and nothing leaves the device.  One `lax.fori_loop`
    over blocks of `chunk` states: each is expanded through the
    backend's own step, every successor's id resolved (a direct table
    where the packed state is small, else the search over the sorted
    fingerprints), and the block's changed rows, compacted by one
    stable sort that carries them, are written where the last block's
    ended: contiguous blocks, no row scatter."""
    from ..engine.backend import require_unconstrained

    require_unconstrained(backend, "the liveness graph capture (PROPERTY)")
    cdc = backend.cdc
    F = cdc.n_fields
    W = (cdc.nbits + 31) // 32
    L = backend.n_lanes
    nbits = cdc.nbits
    V = n_states
    ncand = chunk * L
    n_blocks = -(-V // chunk)
    rows_in = V + min(2 * chunk, ncand)  # the enumerator's `cap + A`
    e_cap = n_edges + ncand
    step = backend.step
    direct = nbits <= DIRECT_ID_BITS
    assert len(backend.labels) < 128, "action ids are kept as int8"

    def init_fn():
        return jnp.zeros((rows_in, W), jnp.uint32)

    def program(states) -> DeviceGraph:
        with jax.named_scope("jaxtlc.live.capture"):
            return capture(states)

    def capture(states) -> DeviceGraph:
        ids = jnp.arange(rows_in, dtype=jnp.int32)
        if direct:
            key = jnp.where(ids < V, states[:, 0].astype(jnp.int32),
                            jnp.int32(1 << nbits))
            table = jnp.full(1 << nbits, -1, jnp.int32).at[key].set(
                ids, mode="drop")

            def id_of(packed):
                return table[packed[:, 0].astype(jnp.int32)]
        else:
            lo, hi = fp64_words_mxu(states[:V], nbits, fp_index, seed)
            s_hi, s_lo, perm = lax.sort(
                (hi, lo, jnp.arange(V, dtype=jnp.int32)), num_keys=2)

            def id_of(packed):
                q_lo, q_hi = fp64_words_mxu(packed, nbits, fp_index, seed)
                at = _pair_searchsorted(s_hi, s_lo, q_hi, q_lo, V)
                at_c = jnp.minimum(at, V - 1)
                found = (s_hi[at_c] == q_hi) & (s_lo[at_c] == q_lo) \
                    & (at < V)
                return jnp.where(found, perm[at_c], -1)

        # rows_in = V + A >= n_blocks * chunk: the last block reads
        # inside the array, its rows past V masked
        rows = jnp.arange(chunk, dtype=jnp.int32)
        lane_src = jnp.arange(ncand, dtype=jnp.int32) // L

        def block(i, st):
            dst, act, deg, n_rows, n_changed, missing = st
            offset = i * chunk
            batch = cdc.unpack(lax.dynamic_slice(
                states, (offset, jnp.int32(0)), (chunk, W)))
            succs, valid, action, _afail, _ovf = jax.vmap(step)(batch)
            valid = (valid & ((offset + rows) < V)[:, None]).reshape(-1)
            to = id_of(cdc.pack(succs.reshape(ncand, F)))
            missing = missing | (valid & (to < 0)).any()
            changed = valid & (to != offset + lane_src)
            # the changed rows to the front, carried by the sort itself
            _, to_c, act_c = lax.sort(
                ((~changed).astype(jnp.uint8), to,
                 jnp.broadcast_to(action, (chunk, L)).reshape(-1)
                 .astype(jnp.int8)),
                num_keys=1, is_stable=True)
            dst = lax.dynamic_update_slice(dst, to_c, (n_changed,))
            act = lax.dynamic_update_slice(act, act_c, (n_changed,))
            deg = lax.dynamic_update_slice(
                deg, changed.reshape(chunk, L).sum(axis=1, dtype=jnp.int32),
                (offset,))
            return (dst, act, deg,
                    n_rows + valid.sum(dtype=jnp.int32),
                    n_changed + changed.sum(dtype=jnp.int32), missing)

        dst, act, deg, n_rows, n_changed, missing = lax.fori_loop(
            0, n_blocks, block,
            (jnp.zeros(e_cap, jnp.int32), jnp.zeros(e_cap, jnp.int8),
             jnp.zeros(n_blocks * chunk, jnp.int32), jnp.int32(0),
             jnp.int32(0), jnp.bool_(False)))
        row_start = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(deg[:V], dtype=jnp.int32)])
        return DeviceGraph(dst, act, row_start, n_rows, n_changed, missing)

    return init_fn, jax.jit(program)


def make_scoped_enumerator(backend, chunk: int, n_states: int,
                           fp_capacity: int,
                           fp_index: int = DEFAULT_FP_INDEX,
                           seed: int = DEFAULT_SEED):
    """(init_fn, program) of the fused enumerator (engine.bfs.
    make_enumerator) with its state array sized to what the safety run
    counted, under the device scope `jaxtlc.live.enumerate`."""
    init_fn, run_fn = make_enumerator(
        backend, chunk=chunk, state_capacity=n_states,
        fp_capacity=fp_capacity, fp_index=fp_index, seed=seed)

    def program(carry):
        with jax.named_scope("jaxtlc.live.enumerate"):
            return run_fn(carry)

    return init_fn, jax.jit(program)


def eval_state_masks(graph: CapturedGraph, cdc, fns, chunk: int = 8192):
    """Evaluate per-state bool predicates over the captured states.

    fns: list of (fields [B, F] -> bool [B]) vectorized predicates; the
    states are unpacked chunk-wise on device so scaled captures never
    materialize the [V, F] field matrix on host.  Returns a list of
    np.bool_ [V] masks aligned with state ids."""
    V = graph.n_states
    W = graph.states.shape[1]
    Vp = -(-max(V, 1) // chunk) * chunk
    pad = jnp.zeros((Vp, W), jnp.uint32).at[:V].set(
        jnp.asarray(graph.states)
    )

    @jax.jit
    def one(offset):
        block = lax.dynamic_slice(pad, (offset, jnp.int32(0)), (chunk, W))
        fields = cdc.unpack(block)
        return [fn(fields) for fn in fns]

    outs = [[] for _ in fns]
    for off in range(0, Vp, chunk):
        res = one(jnp.int32(off))
        for k, r in enumerate(res):
            outs[k].append(np.asarray(r))
    return [np.concatenate(o)[:V] for o in outs]
