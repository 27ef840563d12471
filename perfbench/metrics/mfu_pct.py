"""Model FLOP/s utilisation: operations forward and backward need per
token (matmul parameters and causal attention, no recomputation) times
tokens per second of the traced run's window, over chips times peak."""


def read(ctx):
    cfg, out = ctx["config"], ctx["out"]
    rate = out["metrics"].get("train_tokens_per_s")
    if rate is None:
        return None
    need = ctx["family"].train_flops_per_token(cfg, ctx["traffic"]["seq"])
    peak = ctx["peaks"]["flops_per_s"][cfg["dtype"]] * len(ctx["devices"])
    return 100.0 * need * rate / peak
