"""Load generator (benchmark): 95th percentile over the requests due in
the window of (handed over - due). A starved generator must not be read
as a fast server. Host clock."""

from benchmark.harness import percentile


def read(ctx):
    xs = ctx["window"]["lateness_s"]
    return percentile(xs, 95) * 1e3 if xs else None
