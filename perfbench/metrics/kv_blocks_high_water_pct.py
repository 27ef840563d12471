"""The block allocator's high water as a share of the blocks it can
hand out (one is reserved)."""


def read(ctx):
    e = ctx["out"]["facts"].get("engine")
    if not e or e["num_blocks"] <= 1:
        return None
    return 100.0 * e["kv_high_water"] / (e["num_blocks"] - 1)
