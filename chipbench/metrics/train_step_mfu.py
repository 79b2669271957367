"""Model FLOP/s utilisation of the training step: model FLOPs per token
(from shapes, without recomputation) times the tokens per second of the
window's untraced steps, over the chips' bf16 peak."""


def read(*, summary, flops, config, traffic, peaks, chips, **_):
    tps = summary.get("untraced_tokens_per_s")
    if not tps:
        return None
    per_token = flops.train_flops_per_token(config, traffic["seq_len"])
    return 100.0 * per_token * tps / (chips * peaks["bf16_flops_per_s"])
