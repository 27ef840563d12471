"""Mean self time a CHUNK step spends in the regions `qkv_proj` (norm,
the qkv product, bias, the value scale), `rope`, `q_pack` (the packed q
rows laid into their buffer), `out_proj` (the output product, the
residual, the post-norm) and `embed` (the embedding gather, `live_rows`
and its tile loop), ms a step (`lib/step_regions.py`); nothing under 10
chunk steps or from a program that names none of them."""
import step_regions


def read(ctx):
    return step_regions.group_ms(ctx, "proj")
