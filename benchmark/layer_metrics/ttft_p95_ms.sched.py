"""Scheduler: 95th percentile, over all requests due in the window, of
due -> first token (a request not answered at the close counts at its age
then). Asked for end to end by ISSUE 24; with some 70 requests a window it
stands on the three or four slowest of them and spread by a third across
seeds (PERF.md), so it is read here until a benchmark issue can hold it."""


def read(ctx):
    return ctx["values"].get("ttft_p95_ms")
