"""90th percentile over ALL gaps between consecutive output tokens of a
request inside the window, client clock at `on_token`, ms. Today it lies
inside the level "a decode step behind one prefill" (52% to 95% of the
gaps) and repeats to 0.3% (six runs, PR 25): the steady tail, to be
promoted to an end-to-end metric once two sets of runs have measured
it."""


def read(run):
    return run.e2e.get("tpot_p90_ms")
