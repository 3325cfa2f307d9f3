"""scope_cover_pct: of the largest executable this process keeps (the
check's segment program; jaxtlc/obs/scopes.py `tables()`), the share of
the instructions the device can run on their own - those outside fused
computations and reducers, less `parameter`, `constant`, `tuple`,
`get-tuple-element` and `bitcast` - whose `op_name` lies under a
`jaxtlc.*` scope.  A count, not time: the guard that the scopes still
reach the chip's HLO.  It falls the day a rewrite leaves a scope or a JAX
upgrade stops carrying `op_name` through a fusion, before anyone needs
the scope table.  None where the program has no scope tables (before
PR 37) or keeps no executable."""


def read(run):
    try:
        from jaxtlc.obs import scopes
    except ImportError:
        return None
    tables = scopes.tables()
    if not tables:
        return None
    scoped, total = scopes.cover(
        max(tables, key=lambda t: len(t["instructions"])))
    return 100.0 * scoped / total if total else None
