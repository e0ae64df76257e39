"""Model FLOP utilisation of the whole traced window, in %: 2 FLOPs per
weight of every matrix multiply (the embedding gather left out) times the
tokens the steps fed (prompt and output alike), over the window's length,
chips and the chip's bf16 peak.  Moves ``decode_tokens_per_s``."""

from harness import costs


def read(rec):
    t, c = rec["trace"], rec["counts"]
    if not c["traced_tokens_fed"]:
        return None
    flops = costs.flops_per_token(rec["config"]) * c["traced_tokens_fed"]
    per_s = flops / (t.window_ns / 1e9)
    return 100.0 * per_s / (rec["chips"] * rec["peaks"]["bf16_flops_per_s"])
