"""100 * (1 - union of device-op intervals / traced window), the mean
over the chips used."""
from benchmarks import trace_reduce as tr


def read(ctx, params):
    t0, t1 = ctx["window"]
    shares = [tr.idle_share(d["ops"], t0, t1) for d in ctx["devices"]]
    return 100.0 * sum(shares) / len(shares)
