"""Checkpoint/recovery (E13) - the TLC periodic-checkpoint analog.

TLC periodically snapshots its disk-backed structures (OffHeapDiskFPSet +
DiskStateQueue, /root/reference/KubeAPI.toolbox/Model_1/MC.out:5) so an
interrupted exhaustive run can resume with `-recover`.  The TPU-native
equivalent snapshots the *entire engine carry* - fingerprint table, frontier
ring buffer, level fencing, and all counters (engine.bfs.EngineCarry) - to a
host-side .npz, and resumes by seeding a freshly built engine with the loaded
carry.  Because the engine is a pure function of the carry, resume is exact:
the resumed run reproduces the uninterrupted run's final counts bit-for-bit
(tested in tests/test_checkpoint.py).

The checkpointed driver trades the single fused `lax.while_loop` for a
host loop over an n-chunk fused segment (the engine's own `step_fn.segment`:
a while loop that also ends when the check does), syncing to host once per
segment - the standard checkpoint-granularity
trade-off.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import time
import zlib
from typing import NamedTuple, Optional

import jax
import numpy as np

from ..config import ModelConfig
from ..obs.spans import in_check, span
from .bfs import (
    DEFAULT_FP_HIGHWATER,
    CheckResult,
    EngineCarry,
    carry_done,
    commit_counters,
    commit_geometry,
    make_engine,
    result_from_carry,
)
from .fingerprint import DEFAULT_FP_INDEX, DEFAULT_SEED

# v2: fingerprint-table layout changed from triangular avalanche-hash
# probing to bucketized top-bits-of-hi (fpset v4); a v1 table's rows sit at
# slots the v4 walk never visits, so version skew must be rejected loudly.
# v3: per-array CRC32 manifest in __meta__ (crash-consistency: a torn or
# bit-rotted file is detected at load instead of recovering into garbage)
# + fp_highwater recorded in meta.
FORMAT_VERSION = 3


class CheckpointCorruptError(ValueError):
    """A checkpoint file failed integrity verification (truncated npz,
    CRC mismatch, or missing manifest).  Distinct from plain ValueError
    geometry mismatches so the generation-fallback loader can tell
    'wrong file' (fatal) from 'torn file' (fall back to the previous
    generation)."""


def _meta(cfg: ModelConfig, meta_config: dict = None,
          **engine_params) -> dict:
    # round-trip through JSON so tuple-vs-list differences can't make a
    # fresh meta compare unequal to one loaded from disk; generic specs
    # pass a meta_config dict instead of a ModelConfig
    return json.loads(
        json.dumps(
            {
                "format": FORMAT_VERSION,
                "config": (meta_config if meta_config is not None
                           else dataclasses.asdict(cfg)),
                **engine_params,
            }
        )
    )


def fsync_replace(tmp: str, path: str, f=None) -> None:
    """Durable atomic publish: fsync the tmp file (before the rename, so a
    crash cannot publish a name whose bytes never hit the platter - rename
    alone only orders the metadata), rename, then fsync the directory so
    the rename itself is durable."""
    if f is not None:
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    dirfd = os.open(os.path.dirname(os.path.abspath(path)) or ".",
                    os.O_RDONLY)
    try:
        os.fsync(dirfd)
    finally:
        os.close(dirfd)


def save_checkpoint(path: str, carry, meta: dict) -> None:
    """Crash-consistent snapshot: leaves as npz + json meta with a
    per-array CRC32 manifest, fsync'd tmp-file + rename (torn writes are
    either invisible - the old file survives - or detected at load)."""
    leaves = jax.tree_util.tree_leaves(carry)
    arrays = {f"leaf_{i}": np.asarray(x) for i, x in enumerate(leaves)}
    manifest = {
        k: zlib.crc32(np.ascontiguousarray(a).tobytes())
        for k, a in arrays.items()
    }
    meta = {**meta, "manifest": manifest}
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        np.savez_compressed(f, __meta__=json.dumps(meta), **arrays)
        fsync_replace(tmp, path, f=f)


def read_checkpoint_meta(path: str) -> dict:
    """Read only the meta dict of a checkpoint (no leaf verification).

    The supervisor uses this to rebuild an engine with the GEOMETRY THE
    CHECKPOINT RECORDS (auto-regrown capacities included) before loading
    the leaves, so a resume command never needs to repeat the grown
    sizes.  Raises CheckpointCorruptError on unreadable files."""
    try:
        with np.load(path, allow_pickle=False) as z:
            return json.loads(str(z["__meta__"]))
    except CheckpointCorruptError:
        raise
    except Exception as e:  # truncated zip, missing key, bad json ...
        raise CheckpointCorruptError(f"unreadable checkpoint {path!r}: {e}")


def require_checkpoint(path: Optional[str]) -> None:
    """A resume with no file to load says so BEFORE the engine is
    built, not after a minute of compile."""
    if path is None or not os.path.exists(path):
        raise FileNotFoundError(f"no checkpoint at {path!r}")


def load_checkpoint(path: str, template: EngineCarry):
    """Load + verify a snapshot into the structure of `template` (an
    EngineCarry from the same engine geometry).  Returns (meta, carry).
    Raises CheckpointCorruptError when the file is torn or its arrays
    fail the CRC32 manifest; ValueError on geometry/version mismatch."""
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    try:
        with np.load(path, allow_pickle=False) as z:
            meta = json.loads(str(z["__meta__"]))
            leaves = [
                z[f"leaf_{i}"]
                for i in range(sum(k.startswith("leaf_") for k in z.files))
            ]
    except Exception as e:  # BadZipFile / zlib.error / KeyError / json ...
        # the file-parsing boundary: ANY read failure here means a torn or
        # rotten file, which the generation fallback is built to survive
        raise CheckpointCorruptError(f"unreadable checkpoint {path!r}: {e}")
    manifest = meta.get("manifest")
    if manifest is not None:
        for i, a in enumerate(leaves):
            want = manifest.get(f"leaf_{i}")
            got = zlib.crc32(np.ascontiguousarray(a).tobytes())
            if want is None or got != want:
                raise CheckpointCorruptError(
                    f"checkpoint {path!r} leaf_{i} CRC mismatch "
                    f"({got} != {want}) - torn write or bit rot"
                )
    t_paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    if len(leaves) != len(t_paths):
        # by name: the first leaf of this engine's carry the file does
        # not hold at its place - what a snapshot cut by another
        # version lacks (`.commit_stat` before ISSUE 50) or, with other
        # flags, the first leaf the two carries differ in.  Never
        # padded: a zeroed block would leave the file's bodies out
        first = next(
            (jax.tree_util.keystr(path)
             for got, (path, want) in zip(leaves, t_paths)
             if got.shape != want.shape
             or got.dtype != np.asarray(want).dtype),
            jax.tree_util.keystr(t_paths[min(len(leaves),
                                             len(t_paths) - 1)][0]))
        raise ValueError(
            f"checkpoint has {len(leaves)} leaves, engine expects "
            f"{len(t_paths)} - geometry mismatch, or cut by another "
            f"version: no leaf {first} where the engine has one"
        )
    for got, (path, want) in zip(leaves, t_paths):
        # the carry's own name for the leaf (`.route_stat`): a snapshot
        # cut by an engine whose leaf had another shape is refused by it
        name = jax.tree_util.keystr(path)
        if got.shape != want.shape:
            raise ValueError(
                f"checkpoint leaf {name} shape {got.shape} != engine "
                f"{want.shape} - was the engine built with different "
                "capacities, or by another version?"
            )
        if got.dtype != np.asarray(want).dtype:
            raise ValueError(
                f"checkpoint leaf {name} dtype {got.dtype} != engine "
                f"{np.asarray(want).dtype} - corrupt or version-skewed file"
            )
    return meta, jax.tree_util.tree_unflatten(treedef, leaves)


_GEN_RE = re.compile(r"\.g(\d{6})\.npz$")


def generation_path(base: str, gen: int) -> str:
    """File name of generation `gen` of the checkpoint family `base`."""
    return f"{base}.g{gen:06d}.npz"


def list_generations(base: str):
    """[(gen, path)] of all on-disk generations of `base`, ascending."""
    out = []
    for p in glob.glob(f"{glob.escape(base)}.g??????.npz"):
        m = _GEN_RE.search(p)
        if m:
            out.append((int(m.group(1)), p))
    return sorted(out)


def save_generation(base: str, carry, meta: dict, keep: int = 2) -> str:
    """Write the next generation of the checkpoint family `base`, then
    prune to the newest `keep` generations.  Because the previous
    generation is deleted only AFTER the new one is durably published, a
    torn newest file always leaves a verified-good predecessor to fall
    back to (load_latest_generation walks newest-first)."""
    gens = list_generations(base)
    gen = (gens[-1][0] + 1) if gens else 1
    path = generation_path(base, gen)
    meta = {**meta, "generation": gen}
    save_checkpoint(path, carry, meta)
    for old_gen, old_path in gens[: max(0, len(gens) - (keep - 1))]:
        # a spilling run pairs each generation with a host-tier file
        # (engine.spill.spill_sibling); prune it with its generation
        for victim in (old_path, old_path + ".spill"):
            try:
                os.remove(victim)
            except OSError:
                pass  # pruning is best-effort; never fail a save over it
    return path


def load_latest_generation(base: str, template):
    """Load the newest generation that passes integrity verification.

    Walks generations newest-first; a corrupt (torn/CRC-failing) file is
    skipped with a fallback to its predecessor - the crash-window case
    the generation scheme exists for.  Geometry/config mismatches
    (plain ValueError) still raise: a WRONG checkpoint must never be
    silently skipped.  Returns (path, meta, carry); raises
    FileNotFoundError when no loadable generation exists."""
    gens = list_generations(base)
    last_err = None
    for gen, path in reversed(gens):
        try:
            meta, carry = load_checkpoint(path, template)
            return path, meta, carry
        except CheckpointCorruptError as e:
            last_err = e
    if last_err is not None:
        raise FileNotFoundError(
            f"no intact checkpoint generation under {base!r} "
            f"(newest failure: {last_err})"
        )
    raise FileNotFoundError(f"no checkpoint generations under {base!r}")


@in_check
def check_with_checkpoints(
    cfg: ModelConfig,
    chunk: int = 1024,
    queue_capacity: int = 1 << 15,
    fp_capacity: int = 1 << 20,
    fp_index: int = DEFAULT_FP_INDEX,
    seed: int = DEFAULT_SEED,
    ckpt_path: Optional[str] = None,
    ckpt_every: int = 256,
    resume: bool = False,
    max_segments: Optional[int] = None,
    on_progress=None,
    fp_highwater: float = DEFAULT_FP_HIGHWATER,
    pipeline: bool = False,
    obs_slots: int = 0,
    deferred: bool = None,
) -> CheckResult:
    """Exhaustive check with periodic checkpoints every `ckpt_every` chunks.

    resume=True loads `ckpt_path` (which must exist and match the engine
    geometry + config) and continues; the final counts equal an
    uninterrupted run's.  max_segments stops early (for tests / simulated
    interruption) after that many fused segments, leaving a valid checkpoint
    behind.  on_progress(depth, generated, distinct, queue_left) fires at
    every segment boundary - the TLC mid-run Progress-line analog
    (MC.out:35: TLC prints Progress(level) periodically; the fused
    single-dispatch engine has no sync point to report from, this driver
    does).

    Segment dispatch is asynchronous: the snapshot write and progress
    readback of segment k happen WHILE segment k+1 executes, fencing with
    jax.block_until_ready only at the next boundary - checkpoint/coverage
    readback stays off the device critical path (PERF.md round 7).
    """
    from ..runtime import aot_build, engine_key
    from .bfs import resolve_deferred

    if resume:
        require_checkpoint(ckpt_path)
    deferred = resolve_deferred(deferred, chunk)
    meta = _meta(
        cfg,
        chunk=chunk,
        queue_capacity=queue_capacity,
        fp_capacity=fp_capacity,
        fp_index=fp_index,
        seed=seed,
        fp_highwater=fp_highwater,
        pipeline=pipeline,
        obs_slots=obs_slots,
        deferred=deferred,
    )

    def make():
        # donate=False: segment k's output is serialized to disk while
        # segment k+1 (fed the same arrays) is in flight
        init_fn, _, step_fn = make_engine(
            cfg, chunk, queue_capacity, fp_capacity, fp_index, seed,
            fp_highwater=fp_highwater, pipeline=pipeline, donate=False,
            obs_slots=obs_slots, deferred=deferred,
        )

        return init_fn, step_fn.segment(ckpt_every)

    # kept under the meta a resume compares, and the cadence: read
    # only (donate=False), fed back below as the first carry
    template, compiled_segment = aot_build(
        make, key=engine_key("ckpt", cfg, meta, ckpt_every))
    t0 = time.time()
    if resume:
        saved_meta, carry = load_checkpoint(ckpt_path, template)
        # every parameter that shapes the carry or the fingerprint function
        # must match - including chunk, which sizes the queue padding and
        # the adaptive-step bodies (only the checkpoint CADENCE may change
        # across a resume)
        for key in ("format", "config", "chunk", "queue_capacity",
                    "fp_capacity", "fp_index", "seed", "fp_highwater",
                    "pipeline", "obs_slots", "deferred"):
            # pre-pipeline/pre-obs/pre-deferred snapshots carry no
            # key: treat as off
            saved = saved_meta.get(
                key, False if key in ("pipeline", "deferred")
                else 0 if key == "obs_slots" else None)
            if saved != meta[key]:
                raise ValueError(
                    f"checkpoint {key} mismatch: "
                    f"{saved!r} != {meta[key]!r}"
                )
    else:
        carry = template

    def owed(pending):
        """Snapshot and progress of the boundary `pending` came from."""
        if ckpt_path is not None:
            save_checkpoint(ckpt_path, pending, meta)
        if on_progress is not None and not carry_done(pending):
            st = pending.st_n if pending.st_n is not None else 0
            d, g, di, ln, qh, nn, sn = jax.device_get(
                (pending.depth, pending.generated, pending.distinct,
                 pending.level_n, pending.qhead, pending.next_n, st)
            )
            on_progress(int(d), int(g), int(di),
                        int(ln) - int(qh) + int(nn) + int(sn))

    segments = 0
    pending = None  # carry whose snapshot/progress is owed
    with span("loop"):
        done = carry_done(carry)
        while not done:
            if max_segments is not None and segments >= max_segments:
                break
            with span("loop.dispatch"):
                in_flight = compiled_segment(carry)  # async dispatch
            # host work for the PREVIOUS boundary overlaps the running
            # segment (reading `carry` concurrently is safe: donate=False)
            with span("loop.overlap"):
                if pending is not None:
                    owed(pending)
            with span("loop.wait"):
                carry = jax.block_until_ready(in_flight)
            segments += 1
            pending = carry
            # the supervisor's vocabulary: this loop's one device read;
            # its progress goes out in loop.overlap (owed), so it has no
            # loop.readback.emit
            with span("loop.readback"), span("loop.readback.get"):
                done = carry_done(carry)
        # the last boundary has no next segment to hide behind
        if pending is not None and ckpt_path is not None:
            save_checkpoint(ckpt_path, pending, meta)

    wall = time.time() - t0
    from .fpset import fpset_actual_collision

    with span("check.result") as read:
        afc = float(fpset_actual_collision(carry.fps))
        from ..spec.kernel import lane_layout

        result = result_from_carry(
            carry, wall, iterations=segments,
            commit=commit_geometry(lane_layout(cfg)[1], chunk))
        # the commit's counts, on the one record this entry writes
        read.attrs.update(commit_counters(result))
        return result._replace(actual_fp_collision=afc)
