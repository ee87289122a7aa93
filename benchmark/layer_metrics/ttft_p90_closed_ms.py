"""90th percentile of first-token time minus due time over the requests
whose first token arrived in the window. In a saturated closed loop it is
the prompt's number of chunk steps times the step time, from some tens of
requests: too few for a bound, so it stands here and not among the
end-to-end metrics (the runner also offers it as ``ttft_p90_ms`` to a cell
with enough requests). It is a time the client feels, not a cause of
another; it is tied to the rate by the closed loop itself: ``clients`` =
requests per second x (first-token time + decode time), so a shorter wait
for the first token IS more requests, and tokens, a second."""

LAYER = "entry"
MOVES = "serve_tokens_per_s"


def read(run):
    ttft = run.facts.get("ttft_s")
    if not ttft:
        return None
    return 1e3 * run.stats.percentile(ttft, 90)
