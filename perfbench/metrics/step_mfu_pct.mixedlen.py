"""The whole step's share of the chip's peak: the MODEL's operations for
the tokens the window stepped, over the window's seconds times the
chip's bfloat16 peak (peaks.json). Operations by the family module's
cost functions: per stepped token the projections, the dense GLU and the
routers; per (token, held expert) assignment that fell on this chip the
expert's three matrices; per sampled token the head's vocabulary slice;
per (query, key) pair of each kind of layer q . k and p v over all
heads. Counted from the registry, window's gain: stepped tokens
serve_tokens_stepped_total, assignments on this chip
serve_moe_assignments_total{where=here}, sampled tokens
serve_tokens_total, pairs serve_attn_pairs_total{kind}. What the program
computes beyond that (padding rows, whole blocks where a window needs
part of one, every slot's head row) is not the model's and not counted:
a later claim in this cell is bounded by this share. None for a family
without these cost functions or a program without the counters."""
import readers


def read(ctx):
    fam, cfg = ctx["family"], ctx["config"]
    if not hasattr(fam, "token_matmul_flops"):
        return None
    tokens = readers.counter_delta(ctx, "serve_tokens_stepped_total")
    if not tokens:
        return None
    here = readers.counter_delta(ctx, "serve_moe_assignments_total", "here")
    full, window = fam.attention_pair_flops(cfg)
    ops = (tokens * fam.token_matmul_flops(cfg)
           + here * fam.expert_flops(cfg)
           + readers.counter_delta(ctx, "serve_tokens_total")
           * fam.head_flops(cfg)
           + readers.counter_delta(ctx, "serve_attn_pairs_total", "full")
           * full
           + readers.counter_delta(ctx, "serve_attn_pairs_total", "window")
           * window)
    peak = ctx["peaks"]["flops_per_s"][cfg["dtype"]] * len(ctx["devices"])
    return 100.0 * ops / (ctx["seconds"] * peak)
