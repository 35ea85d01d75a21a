"""95th percentile over ALL gaps between consecutive output tokens of a
request inside the window, client clock at `on_token`, ms. Not an
end-to-end metric: the gaps take a few discrete values (a decode step
alone, a decode step behind one prefill, behind two, ...), so a high
percentile sits on a cliff between two of them and jumps by the
difference from run to run (PERF.md, PR 25). Read it beside
`tpot_p50_ms` for what a prefill between a caller's tokens costs."""


def read(run):
    return run.e2e.get("tpot_p95_ms")
