"""How late the load generator sent requests: sent - due on the benchmark's
clock, 95th percentile over every request sent.  A starved generator must not
be read as a fast server."""
from benchmarks import stats


def read(red, run):
    late = run.get("late_ms")
    return stats.percentile(late, 95)[0] if late else None
