"""signals.ingest_ms: mean wall time of `ControlPlane.ingest` per
service period (the heartbeat store's append), in ms."""


def read(ctx):
    recs = ctx["driver"].records
    if not recs:
        return None
    return 1e3 * sum(r[1] for r in recs) / len(recs)
