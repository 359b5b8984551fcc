"""p90 over the requests due in the window of first token - due time;
a request still without a first token at the close counts its wait."""
import windowstats as ws


def read(run):
    p = ws.percentile(ws.ttfts(run), 90)
    return None if p is None else 1e3 * p
