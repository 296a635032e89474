"""Model FLOPs per token (bench/counts) x tokens/s over chips x bf16 peak,
in %. Tokens/s is the untraced window's."""


def read(r):
    w = r.window
    if not w.get("steps"):
        return None
    per_token = r.counts().train_flops_per_token(r.cell.config, w["seq_len"])
    rate = w["tokens"] / w["window_s"]
    return 100.0 * per_token * rate / (w["chips"] * r.peaks["bf16_flops"])
