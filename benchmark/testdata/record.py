"""How benchmark/testdata/kubeapi-ff-2checks.xplane.pb.gz was recorded (on a
TPU v5e, through the chip tool):

    python3 benchmark/testdata/record.py chiprun_out/trace

Two check_with_checkpoints calls of KubeAPI 1x1 with both fault
constants FALSE, cut to one segment of four steps each so that the trace
stays small, under the profiler, each inside a
`bench:check_with_checkpoints` annotation of the harness's kind, the
whole inside `bench:trace_slice`, host tracer at level 1 as run.py sets
it - a trace small enough to keep in the repo, with device operations,
idle gaps and host spans in it.  Prints what the reducer makes of it;
that output, kept beside the trace as .expect.json, is what
tests/test_trace_reduce.py holds the reducer to.
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, ROOT)
NAME = "kubeapi-ff-2checks"


def main(out_dir: str) -> int:
    import jax

    import trace_reduce
    from jaxtlc.config import make_scaled
    from jaxtlc.engine.checkpoint import check_with_checkpoints
    from jaxtlc.runtime import enable_compile_cache

    enable_compile_cache()
    model = make_scaled(1, 1, False, False)

    def check():
        with jax.profiler.TraceAnnotation("bench:check_with_checkpoints"):
            r = check_with_checkpoints(model, chunk=256,
                                       queue_capacity=1 << 12,
                                       fp_capacity=1 << 15, ckpt_every=4,
                                       max_segments=1)
        assert r.distinct > 2 and r.violation == 0, r

    check()  # warm
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:trace_slice"):
        for _ in range(2):
            check()
            time.sleep(0.05)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(d)
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, NAME + ".xplane.pb.gz")
    with open(path, "rb") as src, gzip.open(dst, "wb", 9) as out:
        shutil.copyfileobj(src, out)
    print("bytes", os.path.getsize(dst), file=sys.stderr)
    from jax.profiler import ProfileData

    for p in ProfileData.from_file(path).planes:
        for ln in p.lines:
            evs = list(ln.events)
            print(f"plane {p.name!r} line {ln.name!r}: {len(evs)} events "
                  f"{sorted({e.name[:40] for e in evs})[:4]}",
                  file=sys.stderr)
    expect = trace_reduce.reduce_file(dst)
    with open(os.path.join(out_dir, NAME + ".expect.json"), "w") as f:
        json.dump(expect, f, indent=1)
    print(json.dumps(expect, indent=1))
    shutil.rmtree(d, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
