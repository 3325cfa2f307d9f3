"""Device-resident liveness: on-device edge capture + tensorized
survive-set fixpoint (the product-graph subsystem SURVEY.md §7.10 named
as the missing piece before scaled configs get temporal checking).

Pipeline (live.check orchestrates):

1. **Enumerate** - the fused append-only state enumerator
   (engine.bfs.make_enumerator) materializes the reachable set on device
   in id order, one `lax.while_loop` dispatch.
2. **Capture** (live.capture) - a vectorized sweep re-expands every state
   through the same kernel, resolves each successor's id with a batched
   binary search over the sorted fingerprints, and emits the successor
   relation as (src, dst, action, state_changing) int32 tensors in
   fixed-capacity chunks, spilling through the checkpoint-style host tier
   when device capacity is exceeded.
3. **Fixpoint** (live.fixpoint) - the Kahn-style greatest-fixpoint
   peeling of engine.liveness, reformulated as converging masked
   scatter-reduce sweeps over the edge tensors inside a `lax.while_loop`,
   optionally sharded over the same mesh as the fingerprint set
   (engine.sharded.sharded_survive_fixpoint).
4. **Lasso** (live.lasso) - prefix + cycle reconstruction from the
   captured edges, validated by host-oracle replay.
"""

from .check import (  # noqa: F401
    HOST_PATH_MAX,
    check_leads_to_device,
    check_properties_device,
    check_struct_properties,
    use_device_path,
)
