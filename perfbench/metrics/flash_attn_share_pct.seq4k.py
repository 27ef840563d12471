"""Share of the device's busy time in the traced window spent in the
flash-attention kernels, forward and backward: the Mosaic custom calls
whose instruction carries a kernel name `flash_attn_*`."""
import readers


def read(ctx):
    return readers.op_share_pct(ctx, "flash_attn_", " custom-call(")
