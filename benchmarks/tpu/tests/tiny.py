"""Cells of BENCHMARK.json shrunk to a size the CPU runs in seconds.

Widths, depth, vocabulary and lengths are cut; the traffic's loop,
clients and engine geometry stay.  The program runs in float32 here, so
a sound run reads logit errors and gaps of float32 rounding, and the
fp8 control and a token altered where it is produced read far above
them.  The limits here are this size's own, set between the two."""
import run as R


def cell(name: str) -> "R.Cell":
    c = R.load_cell(name)
    m = c.config["model"]
    m.update(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
             head_dim=16, vocab_size=256, num_experts=8, top_k=2,
             d_expert=64, dtype="float32")
    if m["num_shared_experts"]:
        m["num_shared_experts"] = 1
    t = c.traffic
    t["prompt"].update(median=20, min=8, max=40)
    t["output"].update(min=4, max=12)
    if "median" in t["output"]:
        t["output"]["median"] = 6
    t["engine"]["chunk_tokens"] = 16
    if t["loop"] == "open":
        t["rate_rps"] = 4.0
    c.limits.update(median_logit_rel_err={"limit": 1e-3},
                    max_gap={"limit": 1.0})
    return c
