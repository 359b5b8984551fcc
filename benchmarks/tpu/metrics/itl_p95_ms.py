"""p95 of every gap between consecutive tokens of one request, pooled
over all requests, in milliseconds."""
import windowstats as ws


def read(run):
    p = ws.percentile(ws.inter_token_gaps(run), 95)
    return None if p is None else 1e3 * p
