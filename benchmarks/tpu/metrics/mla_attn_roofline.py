"""Kernels (models/mla.py): the least time of the latent attention's
required work over the device time of its events, in percent; None
where no event matched.  Both phases are counted on both sides: every
decode step and every prefill chunk (absorbed form over each row's
live latent context, and ``W_kvb`` once per call and layer;
``work_mla.attn_work``), and the events of both the ``mla_decode`` and
the ``mla_prefill`` scope.

The device's op events carry the HLO instruction's text, not the scope
it was traced under (PERF.md section 3), so the latent attention's ops
are matched by their output shapes, which at the cell's geometry only
it produces (checked against the decode and prefill programs compiled
for a described v5e): the page gathers (rows x page_size x latent or
rotary width), the gathered view (batch x context x width) and the
compiler's copies of it, the page scatters (pages x page_size x
width), the absorbed query and latent output (batch x [chunk x] heads
x kv_lora_rank), the scores (batch x context x [chunk x] heads, or
batch x heads x chunk x context) and the heads' outputs before W_o.
Left out, since projections share their shapes: the new positions'
cache entries and the softmax's per-head sums, which are small."""
import serve_window as sw
import work
import work_mla


def pattern(run) -> str:
    """The regex of the latent attention's op events for the run's
    model and engine geometry."""
    m = work_mla.MlaModel.from_config(run.cell.config["model"])
    s = sw.engine_config(None, run.cell.traffic, 0)
    B, K, ps, S = s.max_batch, s.chunk_tokens, s.page_size, s.max_ctx
    NP = S // ps
    pages = s.pool_pages if s.pool_pages is not None else 2 * B * NP
    r, dr, H = m.kv_lora_rank, m.qk_rope_head_dim, m.num_heads
    dv = m.v_head_dim
    w = f"(?:{r}|{dr})"
    shapes = [
        # both phases: page gathers, the gathered view, page scatters
        f"{B * NP},{ps},{w}", f"{B},{S},{w}", f"{pages},{ps},{w}",
        # decode: absorbed query and latent output, scores, heads'
        # output before W_o
        f"{B},{H},{r}", f"{B},{S},{H}", f"{B},{S},1,{H}", f"{H},{dv},{B}",
        # prefill chunk: the same with the chunk's K positions
        f"{B},{K},{H},{r}", f"{B},{H},{K},{S}", f"{B},{S},{K},{H}",
        rf"{H},{B},\d+,\d+,{dv}"]
    lead = r"^%[\w.\-]+ = \(?(?:\w+\[[\d,]*\]\{[^}]*\}, )*"
    return lead + r"(?:bf16|f32)\[(?:" + "|".join(shapes) + r")\]"


def read(run):
    r = run.reduced
    if r is None:
        return None
    least = work_mla.attn_least_seconds(run)
    t = r.kernel_seconds(pattern(run))
    if least is None or t is None:
        return None
    return work.share_percent(least, t)
