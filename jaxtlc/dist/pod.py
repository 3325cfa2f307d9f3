"""The jax.distributed pod driver (ISSUE 19 tentpole).

One process per host joins a coordinator (`init_pod`), the global "fp"
mesh spans every host's devices (`pod_mesh`), and `run_pod` drives the
UNCHANGED sharded engine body over it - per-host fingerprint-space
shards fall out of the owner mapping hi & (D-1) because the mesh lays
device rows out process-major, and the candidate-routing `all_to_all`
crosses DCN at exactly the level-fence seam the deferred collective
already batches.  What this module adds is the host-side distribution
protocol around that body:

* **Per-host journals**: each process writes its own
  ``{base}.h{pid}.journal.jsonl`` (schema-v1 ``pod`` events carry the
  membership + per-host gauges); obs.serve's /runs registry and
  obs.views.merge_journals fold the siblings into one stream.
* **Per-host checkpoints**: each process snapshots only its OWN mesh
  rows (``{base}.h{pid}`` - table/queue bytes never cross hosts), with
  meta recording num_hosts/host_rows so a resume at the wrong width
  fails loudly instead of silently misassembling the fingerprint space.
* **Preemption consensus**: SIGTERM on ANY host raises a pod-wide vote
  (a tiny jitted `pmax` - membership is not elastic inside a dispatch),
  every host checkpoints its shard at the same segment fence, and every
  process exits EXIT_PREEMPTED (75, the supervisor's checkpoint+exit
  convention).
* **Reshard-on-recover**: `reshard_carry` re-partitions a saved pod's
  table fingerprints (unmix -> re-insert, the regrow migration idiom)
  and frontier states (re-fingerprint -> re-route) by the new owner
  mapping hi & (D'-1), so a preempted 4-host run resumes as a 2-host
  run with identical semantics (`--reshard`).
* **Per-host spill lifeboat**: ``spill="on"`` swaps the fused segment
  for ShardedSpillRuntime's expand/probe/commit protocol - one
  SpillStore per process, exact because fingerprint spaces are disjoint
  per device (engine/sharded.py).  Spill + reshard is unsupported (the
  host stores are keyed per-host); resume at the original width.

* **Pod-native observability** (ISSUE 20, closing ROADMAP #1 residue
  (a)): ``obs_slots``/``coverage`` thread the PR 5 counter ring and the
  PR 11 CoveragePlane through the sharded engine, so each host's carry
  holds its own ring + ``cov_counts`` rows (checkpointed with the shard,
  migrated on ``--reshard``).  At every segment fence the driver decodes
  only its ADDRESSABLE ring rows into per-host PARTIAL ``level`` events
  and its local ``cov_counts`` rows into per-host ``coverage`` deltas -
  each tagged with a ``host`` field - plus a ``segment`` timing event,
  so obs.views.fold_pod_levels / obs.coverage can re-sum the sibling
  journals into pod-global counters and obs.trace can render one
  timeline with a process row per host, lanes aligned on the fence
  timestamps.  Pure telemetry: obs-on pod runs are bit-for-bit obs-off
  runs (tests/test_multihost.py::test_pod_obs_coverage_parity pins the
  folded counters against the engine's own).
"""

from __future__ import annotations

import json
import os
import re
import signal
import time
import zlib
from types import SimpleNamespace
from typing import NamedTuple, Optional

import numpy as np

from .. import __version__
from ..config import ModelConfig

EXIT_OK = 0
EXIT_VIOLATION = 12  # TLC ExitStatus safety-violation (cli contract)
EXIT_PREEMPTED = 75  # EX_TEMPFAIL: shard checkpointed, relaunch to resume

DEFAULT_COORDINATOR = "127.0.0.1:12731"

# levels with no new site before the once-per-run saturation event
# fires (the supervisor's coverage_sat_levels default, PR 11)
COVERAGE_SAT_LEVELS = 8

# engine keys a pod resume must always match (mirrors
# check_sharded_with_checkpoints; "spill" shapes the carry leaves)
_ENGINE_KEYS = ("format", "config", "pipeline", "obs_slots", "deferred",
                "symmetry", "por", "spill")
# geometry keys only a --reshard resume may change
_GEOM_KEYS = ("queue_capacity", "fp_capacity", "devices", "num_hosts")

_STAT_FIELDS = ("generated", "distinct", "depth", "qhead", "qtail",
                "level", "cont", "viol", "viol_state", "viol_local",
                "act_gen", "act_dist", "outdeg_hist", "spill_hits",
                "cov_counts")


# ---------------------------------------------------------------------------
# pod bring-up
# ---------------------------------------------------------------------------


def init_pod(coordinator_address: str = DEFAULT_COORDINATOR,
             num_processes: int = 1, process_id: int = 0) -> None:
    """Join the pod BEFORE any other jax call.  On CPU pods the gloo
    collectives backend must be selected before jax.distributed
    initializes (the localhost test topology; TPU pods autodetect and
    skip both knobs when num_processes comes from the runtime)."""
    import jax

    if num_processes <= 1:
        return
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def pod_mesh(devices: int = None):
    """The global single-axis "fp" mesh over EVERY pod device, in the
    process-major order jax.devices() reports - so the owner partition
    hi & (D-1) assigns each host a contiguous row block.  `devices`
    truncates to the first N devices (single-process width-change
    tests; a real pod always meshes every device)."""
    from ..runtime import fp_mesh

    mesh = fp_mesh(devices or 0)
    assert mesh.size & (mesh.size - 1) == 0, (
        "pod device count must be a power of two "
        f"(got {mesh.size}: set --xla_force_host_platform_device_count "
        "or adjust the host count)"
    )
    return mesh


def host_checkpoint_path(base: str, host: int) -> str:
    return f"{base}.h{host}"


def host_journal_path(base: str, host: int) -> str:
    return f"{base}.h{host}.journal.jsonl"


class _SigtermFlag:
    """SIGTERM -> cooperative stop flag, checked at segment fences (the
    dispatch in flight always completes; membership is not elastic
    inside a collective)."""

    def __init__(self):
        self.hit = False
        self._prev = None

    def _handler(self, signum, frame):
        self.hit = True

    def install(self):
        try:
            self._prev = signal.signal(signal.SIGTERM, self._handler)
        except ValueError:  # not the main thread (serve workers)
            self._prev = None

    def uninstall(self):
        if self._prev is not None:
            try:
                signal.signal(signal.SIGTERM, self._prev)
            except ValueError:
                pass


# ---------------------------------------------------------------------------
# collective helpers (tiny jitted shard_maps over the pod mesh)
# ---------------------------------------------------------------------------


def _first_row(arr):
    """Any addressable row of a [D, ...]-sharded array (for leaves the
    engine keeps replicated across the axis: cont/viol/level)."""
    from ..engine.sharded import shard_host_rows

    rows = shard_host_rows(arr)
    return rows[min(rows)]


def _host_value_array(mesh, value: int):
    """[D] int32 global array where THIS process's rows carry `value`
    (each host votes through its own mesh rows)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    D = int(mesh.devices.size)
    (axis,) = mesh.axis_names
    v = np.int32(value)

    def cb(idx):
        s = idx[0]
        stop = s.stop if s.stop is not None else D
        return np.full(stop - (s.start or 0), v, np.int32)

    return jax.make_array_from_callback(
        (D,), NamedSharding(mesh, P(axis)), cb
    )


def make_stop_vote(mesh):
    """Pod-wide preemption consensus: pmax over per-host stop flags, so
    one SIGTERM stops every host at the SAME segment fence."""
    import jax
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    (axis,) = mesh.axis_names
    fn = jax.jit(shard_map(
        lambda flag: lax.pmax(flag[0], axis)[None],
        mesh=mesh, in_specs=(P(axis),), out_specs=P(axis),
        check_vma=False,
    ))

    def vote(local_hit: bool) -> bool:
        if jax.process_count() == 1:
            return bool(local_hit)
        out = fn(_host_value_array(mesh, 1 if local_hit else 0))
        return bool(int(np.asarray(_first_row(out))))

    return vote


def make_stats_gather(mesh, carry):
    """Host access to the FULL [D, ...] statistic leaves on every
    process (all_gather over the mesh; table/queue stay sharded - only
    the O(D) counter rows cross DCN).  The gathered namespace feeds
    result_from_shard_carry unchanged, so pod statistics reduce with
    bit-identical semantics to the single-process path."""
    import jax
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    from ..engine.sharded import shard_host_rows

    (axis,) = mesh.axis_names
    fields = [f for f in _STAT_FIELDS
              if getattr(carry, f, None) is not None]
    fn = jax.jit(shard_map(
        lambda *xs: tuple(lax.all_gather(x[0], axis)[None] for x in xs),
        mesh=mesh,
        in_specs=tuple(P(axis) for _ in fields),
        out_specs=tuple(P(axis) for _ in fields),
        check_vma=False,
    ))

    def gather(c) -> SimpleNamespace:
        if jax.process_count() == 1:
            return SimpleNamespace(
                **{f: np.asarray(getattr(c, f)) for f in fields}
            )
        outs = fn(*[getattr(c, f) for f in fields])
        vals = {}
        for f, o in zip(fields, outs):
            rows = shard_host_rows(o)
            vals[f] = np.asarray(rows[min(rows)])
        return SimpleNamespace(**vals)

    return gather


# ---------------------------------------------------------------------------
# per-host shard checkpoints
# ---------------------------------------------------------------------------


def save_pod_checkpoint(base: str, carry, meta: dict, host: int) -> str:
    """Snapshot THIS host's mesh rows to ``{base}.h{host}`` (the
    checkpoint.save_checkpoint format: CRC-manifested npz + json meta).
    Meta records num_hosts / host_rows / pod_fields so resume validates
    the partition before touching a single leaf."""
    from ..engine.checkpoint import save_checkpoint
    from ..engine.sharded import shard_host_rows

    rows = {f: shard_host_rows(getattr(carry, f))
            for f in carry._fields if getattr(carry, f) is not None}
    ids = sorted(rows["table"])
    payload = {f: np.stack([r[i] for i in ids]) for f, r in rows.items()}
    # tree_leaves flattens the dict in sorted-key order; pin that order
    # in meta so the shard loader can name leaves without a template
    m = dict(meta, host=host, host_rows=[int(i) for i in ids],
             pod_fields=sorted(payload))
    path = host_checkpoint_path(base, host)
    save_checkpoint(path, payload, m)
    return path


def _load_host_payload(path: str):
    """One shard file -> (meta, {field: [rows, ...] np}), CRC-verified."""
    from ..engine.checkpoint import CheckpointCorruptError

    try:
        with np.load(path, allow_pickle=False) as z:
            meta = json.loads(str(z["__meta__"]))
            leaves = [z[f"leaf_{i}"] for i in range(
                sum(k.startswith("leaf_") for k in z.files))]
    except Exception as e:
        raise CheckpointCorruptError(f"unreadable pod shard {path!r}: {e}")
    manifest = meta.get("manifest") or {}
    for i, a in enumerate(leaves):
        want = manifest.get(f"leaf_{i}")
        got = zlib.crc32(np.ascontiguousarray(a).tobytes())
        if want is None or got != want:
            raise CheckpointCorruptError(
                f"pod shard {path!r} leaf_{i} CRC mismatch "
                f"({got} != {want}) - torn write or bit rot"
            )
    fields = meta.get("pod_fields")
    if fields is None or len(fields) != len(leaves):
        raise ValueError(
            f"{path!r} is not a pod shard checkpoint (no pod_fields "
            "manifest) - whole-carry snapshots resume through "
            "check_sharded_with_checkpoints instead"
        )
    return meta, dict(zip(fields, leaves))


def _host_paths(base: str):
    """Every ``{base}.h<digits>`` shard file, host-ordered (journal
    siblings excluded by the anchored pattern)."""
    pat = re.compile(re.escape(os.path.basename(base)) + r"\.h(\d+)$")
    d = os.path.dirname(os.path.abspath(base)) or "."
    out = {}
    for name in os.listdir(d):
        m = pat.fullmatch(name)
        if m:
            out[int(m.group(1))] = os.path.join(d, name)
    return [out[k] for k in sorted(out)]


def load_pod_full(base: str):
    """Reassemble the FULL [D_old] host-side carry from every per-host
    shard file (shared filesystem: the localhost pod and NFS-backed TPU
    pods both qualify).  Returns (meta_of_host0, numpy ShardCarry)."""
    from ..engine.sharded import ShardCarry

    paths = _host_paths(base)
    if not paths:
        raise FileNotFoundError(f"no pod checkpoint shards at {base!r}.h*")
    rows: dict = {}
    m0 = None
    for p in paths:
        m, payload = _load_host_payload(p)
        if m0 is None:
            m0 = m
        for f, arr in payload.items():
            for k, rid in enumerate(m["host_rows"]):
                rows.setdefault(f, {})[int(rid)] = arr[k]
    d_old = int(m0["devices"])
    short = sorted(f for f, r in rows.items() if len(r) != d_old)
    if short:
        raise ValueError(
            f"pod checkpoint {base!r} is missing shard rows for {short} "
            f"- a {m0.get('num_hosts')}-host snapshot needs every host's "
            ".h* file on this filesystem"
        )
    full = {f: np.stack([r[i] for i in range(d_old)])
            for f, r in rows.items()}
    return m0, ShardCarry(**{f: full.get(f) for f in ShardCarry._fields})


def _validate_pod_meta(saved: dict, want: dict, reshard: bool) -> None:
    """Loud meta gate before any leaf is touched.  Plain resume pins
    engine AND geometry keys (a snapshot only reloads at its own pod
    width); --reshard relaxes exactly the geometry keys that
    reshard_carry re-derives."""
    defaults = {"pipeline": False, "deferred": False, "symmetry": False,
                "por": False, "spill": False, "obs_slots": 0,
                "num_hosts": 1}
    for key in _ENGINE_KEYS + (() if reshard else _GEOM_KEYS):
        s = saved.get(key, defaults.get(key))
        if s != want[key]:
            hint = (
                "; a pod snapshot resumes only at the width that cut it "
                "- relaunch with --reshard to re-partition the "
                "fingerprint space" if key in ("devices", "num_hosts")
                else ""
            )
            raise ValueError(
                f"checkpoint {key} mismatch: {s!r} != {want[key]!r}{hint}"
            )


# ---------------------------------------------------------------------------
# reshard-on-recover
# ---------------------------------------------------------------------------


def reshard_carry(carry, backend, d_new: int,
                  queue_capacity: int = None, fp_capacity: int = None,
                  fp_index: int = None, seed: int = None):
    """Re-partition a full host-side numpy ShardCarry from D_old to
    `d_new` mesh rows under the new owner mapping hi & (d_new - 1).

    Tables: stored words are unmixed back to raw fingerprints (the
    regrow-migration idiom) and re-inserted into the new owner's table,
    so the new stored words are bit-identical to what a fresh run of
    the new width would hold; per-device `distinct` becomes the new
    occupancy (their sum is preserved - verified).  Queues: the live
    window [qhead, qtail) is split at the level boundary, each state is
    re-fingerprinted and routed to its new owner, current-level states
    pack before next-level states, and the head renumbers to 0 (the
    regrow queue-renumber idiom) - so level/depth accounting continues
    exactly.  Scalar replicated leaves copy through; partial counters
    sum into row 0 (owner attribution of PAST counts is bookkeeping
    only - totals are what the result reduces).

    Like the regrow migration, the (0,0)->(1,0) mixed-word remap class
    re-routes by its unmixed preimage, a 2^-64-probability attribution
    quirk with no effect on stored words or counts.
    """
    from ..engine.fingerprint import (
        DEFAULT_FP_INDEX, DEFAULT_SEED, fp64_words,
    )
    from ..engine.fpset import host_insert, unmix_host
    from ..engine.sharded import ROUTE_STAT_COLS, ShardCarry

    import jax.numpy as jnp

    fp_index = DEFAULT_FP_INDEX if fp_index is None else fp_index
    seed = DEFAULT_SEED if seed is None else seed
    if d_new & (d_new - 1):
        raise ValueError(f"pod width must be a power of two, got {d_new}")
    for f in ("pv_n", "spill_hits"):
        if getattr(carry, f, None) is not None:
            raise ValueError(
                f"reshard does not support carries with {f} (pipelined/"
                "spill pod snapshots resume at their own width)"
            )
    table = np.asarray(carry.table)
    queue = np.asarray(carry.queue)
    d_old = table.shape[0]
    F = queue.shape[-1]
    qcap = int(queue_capacity or (queue.shape[1] - 1))
    fpcap = int(fp_capacity or table.shape[1] * 8)

    # fingerprint tables: unmix -> re-insert by the new owner bits
    table2 = np.zeros((d_new, fpcap // 8, 16), np.uint32)
    distinct2 = np.zeros(d_new, np.uint32)
    for d in range(d_old):
        lo = table[d][:, 0::2].reshape(-1)
        hi = table[d][:, 1::2].reshape(-1)
        occ = (lo != 0) | (hi != 0)
        raw_lo, raw_hi = unmix_host(lo[occ], hi[occ])
        for rl, rh in zip(raw_lo.tolist(), raw_hi.tolist()):
            nd = int(rh) & (d_new - 1)
            if host_insert(table2[nd], int(rl), int(rh)):
                distinct2[nd] += 1
    total = int(np.asarray(carry.distinct, np.int64).sum())
    if int(distinct2.sum()) != total:
        raise ValueError(
            f"reshard integrity: re-inserted {int(distinct2.sum())} "
            f"fingerprints but the snapshot holds {total} distinct - "
            "corrupt shard or fp_capacity too small for the new width"
        )

    # frontier queues: split the live window at the level boundary,
    # route each state to its new fingerprint owner, head renumbers to 0
    qhead = np.asarray(carry.qhead)
    qtail = np.asarray(carry.qtail)
    lend = np.asarray(carry.level_end)
    cur_rows, nxt_rows = [], []
    for d in range(d_old):
        qh, qt, le = int(qhead[d]), int(qtail[d]), int(lend[d])
        # the queue is a ring of its first queue.shape[1] - 1 rows
        live = queue[d, (qh + np.arange(qt - qh)) % (queue.shape[1] - 1)]
        ncur = max(0, min(le, qt) - qh)
        cur_rows.append(live[:ncur])
        nxt_rows.append(live[ncur:])

    def owners(states):
        if len(states) == 0:
            return np.zeros(0, np.int64)
        packed = backend.cdc.pack(jnp.asarray(states))
        _lo, hi = fp64_words(packed, backend.cdc.nbits, fp_index, seed)
        return np.asarray(hi).astype(np.int64) & (d_new - 1)

    queue2 = np.zeros((d_new, qcap + 1, F), np.int32)
    qtail2 = np.zeros(d_new, np.int32)
    lend2 = np.zeros(d_new, np.int32)
    for phase, chunks in (("cur", cur_rows), ("nxt", nxt_rows)):
        states = (np.concatenate(chunks) if chunks
                  else np.zeros((0, F), np.int32))
        own = owners(states)
        for d in range(d_new):
            sel = states[own == d]
            n = len(sel)
            if int(qtail2[d]) + n > qcap:
                raise ValueError(
                    f"resharded frontier does not fit: new device {d} "
                    f"needs {int(qtail2[d]) + n} queue rows > "
                    f"queue_capacity {qcap} - rerun with a larger "
                    "--queue-capacity (reshard re-derives geometry)"
                )
            queue2[d, qtail2[d]:qtail2[d] + n] = sel
            qtail2[d] += n
        if phase == "cur":
            lend2 = qtail2.copy()

    def row0(x):
        x = np.asarray(x)
        out = np.zeros((d_new,) + x.shape[1:], x.dtype)
        out[0] = x.sum(axis=0)
        return out

    def repl(x):
        x = np.asarray(x)
        return np.full((d_new,) + x.shape[1:], x[0], x.dtype)

    vs2 = np.zeros((d_new, F), np.int32)
    vl2 = np.zeros(d_new, bool)
    vl = np.asarray(carry.viol_local)
    if vl.any():
        vs2[0] = np.asarray(carry.viol_state)[int(np.argmax(vl))]
        vl2[0] = True

    extra = {}
    if getattr(carry, "cov_counts", None) is not None:
        extra["cov_counts"] = row0(carry.cov_counts)
    if getattr(carry, "obs_ring", None) is not None:
        # the ring's per-level rows are attributions of PAST partials -
        # like the row-0 counters above they are bookkeeping, not state;
        # the new width starts a fresh ring.  Only the STICKY flags must
        # survive: sticky_overflow reads the max over the WHOLE ring
        # (dump row included), so writing the old pod's flag maxima
        # into every new dump row keeps overflow/cert/sym sticky across
        # the reshard.  Heads replicate the old minimum so the resumed
        # driver's decode cursor (restored local min head) sees no
        # phantom rows in the zeroed region.
        from ..obs.counters import COL_CERT, COL_OVERFLOW, COL_SYM

        ring = np.asarray(carry.obs_ring)
        ring2 = np.zeros((d_new,) + ring.shape[1:], ring.dtype)
        for col in (COL_OVERFLOW, COL_CERT, COL_SYM):
            ring2[:, -1, col] = ring[:, :, col].max()
        heads = np.asarray(carry.obs_head)
        extra["obs_ring"] = ring2
        extra["obs_head"] = np.full(d_new, heads.min(), heads.dtype)
        extra["obs_bodies"] = row0(carry.obs_bodies)
        extra["obs_expanded"] = row0(carry.obs_expanded)
    if getattr(carry, "route_stat", None) is not None:
        # owner-routing telemetry (fullest bucket, bodies run): the
        # same maxima on every new row
        stat = np.asarray(carry.route_stat)
        if stat.shape[1] != ROUTE_STAT_COLS:
            raise ValueError(
                f"checkpoint leaf 'route_stat' has {stat.shape[1]} "
                f"columns, this engine's {ROUTE_STAT_COLS} - cut by "
                "another version"
            )
        extra["route_stat"] = np.tile(stat.max(axis=0), (d_new, 1))
    if getattr(carry, "commit_stat", None) is None:
        raise ValueError(
            "checkpoint lacks leaf 'commit_stat' of this engine's "
            "carry - cut by another version"
        )
    # the commit's counts: partial counters like the others
    extra["commit_stat"] = row0(carry.commit_stat)
    return ShardCarry(
        table=table2,
        queue=queue2,
        qhead=np.zeros(d_new, np.int32),
        qtail=qtail2,
        level_end=lend2,
        level=repl(carry.level),
        depth=repl(carry.depth),
        generated=row0(carry.generated),
        distinct=distinct2,
        act_gen=row0(carry.act_gen),
        act_dist=row0(carry.act_dist),
        outdeg_hist=row0(carry.outdeg_hist),
        viol=repl(carry.viol),
        viol_state=vs2,
        viol_local=vl2,
        cont=repl(carry.cont),
        **extra,
    )


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------


class PodResult(NamedTuple):
    result: object  # engine.bfs.CheckResult
    exit_code: int
    host: int
    hosts: int
    segments: int
    resumed: bool
    resharded: bool
    checkpoint: Optional[str]
    spilled: int = 0
    spill_flushes: int = 0


def run_pod(
    cfg: ModelConfig = None,
    backend=None,
    *,
    chunk: int = 512,
    queue_capacity: int = 1 << 14,
    fp_capacity: int = 1 << 18,
    fp_index: int = None,
    seed: int = None,
    route_factor: float = 2.0,
    deferred: bool = None,
    obs_slots: int = 0,
    coverage: bool = False,
    ckpt_path: str = None,
    ckpt_every: int = 64,
    resume: bool = False,
    reshard: bool = False,
    spill: str = "off",
    spill_capacity: int = 1 << 22,
    fp_highwater: float = None,
    max_segments: int = None,
    meta_config: dict = None,
    workload: str = "kubeapi",
    journal: bool = True,
    progress_every: int = 1,
    on_event=None,
    devices: int = None,
) -> PodResult:
    """Drive one pod member to completion (or preemption) and return
    this process's PodResult.  Must run AFTER init_pod; every process
    of the pod calls it with IDENTICAL parameters (the collectives and
    make_array_from_callback constructors are pod-synchronous).

    chunk/queue_capacity/fp_capacity are PER DEVICE, exactly the
    sharded-engine contract - a pod of H hosts multiplies total table
    capacity by H at constant per-host memory (a CPU drill so far:
    never run across hosts on chips).

    obs_slots > 0 turns the device counter ring on (per-host PARTIAL
    `level` events with a `host` field, decoded from this process's
    ring rows at each fence); coverage=True attaches the workload's
    CoveragePlane (per-host `coverage` delta events).  Both are pure
    telemetry - obs-on results are bit-for-bit obs-off results
    (tests/test_multihost.py::test_pod_obs_coverage_parity)."""
    import jax

    from ..engine.bfs import resolve_deferred
    from ..engine.checkpoint import _meta, read_checkpoint_meta
    from ..engine.fingerprint import DEFAULT_FP_INDEX, DEFAULT_SEED
    from ..engine.sharded import (
        carry_to_global, cov_totals_local, kubeapi_backend,
        make_sharded_engine, obs_rows_sharded_local,
        result_from_shard_carry, shard_host_rows, shard_replace_rows,
        ShardedSpillRuntime,
    )
    from ..obs.coverage import coverage_delta_event

    fp_index = DEFAULT_FP_INDEX if fp_index is None else fp_index
    seed = DEFAULT_SEED if seed is None else seed
    if devices is not None and jax.process_count() > 1:
        raise ValueError("`devices` truncation is a single-process "
                         "test knob; a pod meshes every device")
    mesh = pod_mesh(devices)
    host, hosts = jax.process_index(), jax.process_count()
    D = int(mesh.devices.size)
    if cfg is None and backend is None:
        cfg = ModelConfig()
    if backend is None:
        backend = kubeapi_backend(cfg, coverage=coverage)
    if cfg is None and meta_config is None:
        meta_config = {"backend": "custom"}
    spill_on = spill == "on"
    if spill_on and reshard:
        raise ValueError(
            "spill + reshard is unsupported: per-host SpillStores are "
            "keyed to the width that cut them - resume at the original "
            "width (ROADMAP #1 residue)"
        )
    deferred = resolve_deferred(deferred, chunk)
    red = getattr(backend, "reduce", None)
    meta = _meta(
        cfg if cfg is not None else ModelConfig(),
        meta_config=meta_config,
        queue_capacity=queue_capacity,
        fp_capacity=fp_capacity,
        devices=D,
        pipeline=False,
        obs_slots=obs_slots,
        deferred=deferred,
        symmetry=bool(red is not None and red.plan is not None),
        por=bool(red is not None and red.por and red.safe_ids),
        spill=spill_on,
        num_hosts=hosts,
    )

    jr = None
    if journal and ckpt_path is not None:
        from ..obs.journal import RunJournal

        jr = RunJournal(host_journal_path(ckpt_path, host),
                        resume=resume)

    def emit(kind, **fields):
        if jr is not None:
            jr.event(kind, **fields)
        if on_event is not None:
            on_event(kind, dict(fields))

    # resume validation FIRST: a wrong-width or wrong-mode snapshot
    # must refuse before the engine pays its AOT compile, not after
    resume_full = None
    if resume:
        if ckpt_path is None:
            raise ValueError("resume requires a checkpoint base path")
        my_path = host_checkpoint_path(ckpt_path, host)
        if reshard:
            resume_full = load_pod_full(ckpt_path)
            _validate_pod_meta(resume_full[0], meta, reshard=True)
            if resume_full[0].get("spill"):
                raise ValueError(
                    "reshard of a spill-mode pod checkpoint is "
                    "unsupported - resume at the original width"
                )
        else:
            _validate_pod_meta(read_checkpoint_meta(my_path), meta,
                               reshard=False)

    # engine: the fused AOT segment loop, or the spill runtime's
    # expand/probe/commit protocol when the per-host lifeboat is on
    store = None
    rt = None
    if spill_on:
        from ..engine.spill import SpillStore

        store = SpillStore(spill_capacity)
        rt = ShardedSpillRuntime(
            cfg, mesh, chunk, queue_capacity, fp_capacity,
            fp_index=fp_index, seed=seed, route_factor=route_factor,
            backend=backend, fp_highwater=fp_highwater,
            obs_slots=obs_slots, deferred=deferred, store=store,
            on_event=lambda kind, info: emit(kind, host=host, **info),
        )
        template = rt.init_fn()
        seg = rt.segment_fn(ckpt_every)
    else:
        init_fn, seg_fn = make_sharded_engine(
            cfg, mesh, chunk, queue_capacity, fp_capacity,
            fp_index=fp_index, seed=seed, route_factor=route_factor,
            segment=ckpt_every, backend=backend, deferred=deferred,
            obs_slots=obs_slots,
        )
        template = init_fn()
        if hosts > 1:
            template = carry_to_global(mesh, template)
        seg = seg_fn.lower(template).compile()

    resumed = resharded = False
    carry = template
    if resume:
        if reshard:
            m0, carry_old = resume_full
            np_new = reshard_carry(
                carry_old, backend, D, queue_capacity=queue_capacity,
                fp_capacity=fp_capacity, fp_index=fp_index, seed=seed,
            )
            carry = carry_to_global(mesh, np_new)
            resharded = True
            emit("pod", phase="reshard", host=host, hosts=hosts,
                 old_hosts=int(m0.get("num_hosts", 1)), new_hosts=hosts,
                 old_devices=int(m0["devices"]), new_devices=D)
        else:
            m, payload = _load_host_payload(my_path)
            ids = [int(i) for i in m["host_rows"]]
            cur = sorted(shard_host_rows(template.table))
            if ids != cur:
                raise ValueError(
                    f"checkpoint host_rows mismatch: host {host} owns "
                    f"rows {cur} but the shard file holds {ids} - "
                    "launch hosts in their original order or --reshard"
                )
            lacking = [f for f in template._fields
                       if getattr(template, f) is not None
                       and f not in payload]
            if lacking:
                # never padded: a leaf this engine carries and the
                # file lacks (`commit_stat` before ISSUE 50) would
                # resume with counts that leave the file's bodies out
                raise ValueError(
                    f"checkpoint lacks leaf {lacking[0]!r} of this "
                    "engine's carry - cut by another version"
                )
            for f, arr in payload.items():
                leaf = getattr(carry, f, None)
                if leaf is None:
                    raise ValueError(
                        f"checkpoint leaf {f!r} has no home in this "
                        "engine's carry - meta validation should have "
                        "caught this (corrupt shard?)"
                    )
                if arr.shape[1:] != leaf.shape[1:]:
                    raise ValueError(
                        f"checkpoint leaf {f!r} shape {arr.shape[1:]} a "
                        f"device != engine {leaf.shape[1:]} - cut at "
                        "other capacities, or by another version"
                    )
                carry = carry._replace(**{f: shard_replace_rows(
                    leaf, {i: arr[k] for k, i in enumerate(ids)}
                )})
            if spill_on:
                from ..engine.spill import SpillStore, spill_sibling

                sib = spill_sibling(my_path)
                if os.path.exists(sib):
                    rt.store = store = SpillStore.load(sib)
        resumed = True
        emit("run_resume", version=__version__, path=my_path)
    else:
        emit("run_start", version=__version__, workload=workload,
             engine="pod", device=jax.devices()[0].platform,
             params=dict(chunk=chunk, queue_capacity=queue_capacity,
                         fp_capacity=fp_capacity, devices=D,
                         hosts=hosts, route_factor=route_factor,
                         deferred=deferred,
                         spill=spill_on, obs_slots=obs_slots,
                         coverage=(getattr(backend, "coverage", None)
                                   is not None)))
    emit("pod", phase="join", host=host, hosts=hosts)

    gather = make_stats_gather(mesh, carry)
    vote = make_stop_vote(mesh)

    # per-host obs cursors: each fence decodes only THIS process's new
    # ring rows / coverage movement (no extra collective - the fold
    # back to pod-global totals happens in obs.views over the sibling
    # journals).  fp_load is the host partial over the GLOBAL capacity
    # so the fold can SUM loads.  On resume the cursors seed from the
    # restored carry: journal and checkpoint are written at the same
    # fence, so replaying from the snapshot appends exactly the rows
    # the interrupted journal does not already hold.
    cov_plane = getattr(backend, "coverage", None)
    fp_total = fp_capacity * D
    obs_since = 0
    cov_seen = None
    cov_visited = cov_level = cov_last_new_level = 0
    cov_saturated = False
    if resumed:
        if obs_slots:
            _, obs_since = obs_rows_sharded_local(carry, since=1 << 30)
        if cov_plane is not None:
            cov_seen = cov_totals_local(carry)
            if cov_seen is not None:
                cov_visited = int((cov_seen > 0).sum())
        cov_level = cov_last_new_level = int(
            np.asarray(_first_row(carry.level))
        )

    def save_all(c, label="segment"):
        ts = time.time()
        path = save_pod_checkpoint(ckpt_path, c, meta, host)
        if store is not None:
            from ..engine.spill import spill_sibling

            store.save(spill_sibling(path))
        emit("checkpoint", path=path, seconds=time.time() - ts,
             label=label, host=host)
        return path

    flag = _SigtermFlag()
    flag.install()
    t0 = time.time()
    segments = 0
    preempted = False
    last_ckpt = None
    try:
        while bool(np.asarray(_first_row(carry.cont))):
            if max_segments is not None and segments >= max_segments:
                break
            t_dispatch = time.time()
            carry = jax.block_until_ready(seg(carry))
            t_fence = time.time()
            segments += 1
            tx = time.time()
            stop_now = vote(flag.hit)
            exchange_us = (time.time() - tx) * 1e6
            # obs at EVERY fence (checkpoint cadence, NOT progress
            # cadence): resume replays from the same fence the journal
            # last recorded, so the cursors give exactly-once rows
            if obs_slots:
                rows, obs_since = obs_rows_sharded_local(
                    carry, labels=backend.labels, since=obs_since,
                    fp_capacity_total=fp_total)
                for row in rows:
                    emit("level", host=host, **row)
                if rows:
                    cov_level = max(cov_level, rows[-1]["level"])
            if cov_plane is not None:
                totals = cov_totals_local(carry)
                payload = coverage_delta_event(
                    cov_plane.sites, totals, cov_seen)
                if payload is not None:
                    emit("coverage", host=host, **payload)
                    cov_seen = totals
                    if payload["visited"] > cov_visited:
                        cov_visited = payload["visited"]
                        cov_last_new_level = cov_level
                if (not cov_saturated and cov_visited
                        and cov_level - cov_last_new_level
                        >= COVERAGE_SAT_LEVELS):
                    cov_saturated = True
                    emit("coverage", host=host, visited=cov_visited,
                         sites=len(cov_plane.sites), delta={},
                         saturated=True, level=cov_level)
            # the last of the fence's rows, as the supervisor writes it:
            # the trace exporter draws the levels above at this fence
            emit("segment", index=segments - 1, host=host,
                 t_dispatch=t_dispatch, t_fence=t_fence,
                 wall_s=round(t_fence - t_dispatch, 6))
            if progress_every and segments % progress_every == 0:
                st = gather(carry)
                emit("progress", depth=int(st.depth.max()),
                     generated=int(st.generated.sum()),
                     distinct=int(st.distinct.sum()),
                     queue=int((st.qtail - st.qhead).sum()))
                local = shard_host_rows(carry.distinct)
                emit("pod", phase="stats", host=host, hosts=hosts,
                     shard_occupancy=(
                         max(int(v) for v in local.values())
                         / float(fp_capacity)),
                     spill_bytes=(store.count * 8
                                  if store is not None else 0),
                     exchange_us=exchange_us)
            if ckpt_path is not None:
                last_ckpt = save_all(carry)
            if stop_now:
                preempted = True
                break
    finally:
        flag.uninstall()
    wall = time.time() - t0

    st = gather(carry)
    result = result_from_shard_carry(
        st, wall, iterations=segments, labels=backend.labels,
        viol_names=backend.viol_names,
        fp_capacity_total=fp_capacity * D,
        sites=(cov_plane.sites if cov_plane is not None else None),
    )
    done = not bool(np.asarray(_first_row(carry.cont)))
    if preempted:
        emit("interrupted", signum=int(signal.SIGTERM), path=last_ckpt,
             generated=result.generated, distinct=result.distinct,
             queue=result.queue_left, wall_s=wall)
        emit("pod", phase="leave", host=host, hosts=hosts,
             path=last_ckpt)
        verdict, exit_code = "interrupted", EXIT_PREEMPTED
    elif result.violation:
        verdict, exit_code = "violation", EXIT_VIOLATION
    elif done:
        verdict, exit_code = "ok", EXIT_OK
    else:  # max_segments pause: journal closes valid, resume continues
        verdict, exit_code = "interrupted", EXIT_OK
    emit("final", verdict=verdict, generated=result.generated,
         distinct=result.distinct, depth=result.depth,
         queue=result.queue_left, wall_s=wall,
         interrupted=not (done or result.violation != 0))
    if jr is not None:
        jr.close()
    return PodResult(
        result=result, exit_code=exit_code, host=host, hosts=hosts,
        segments=segments, resumed=resumed, resharded=resharded,
        checkpoint=last_ckpt,
        spilled=(store.count if store is not None else 0),
        spill_flushes=(rt.flushes if rt is not None else 0),
    )
