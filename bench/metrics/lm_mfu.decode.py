"""Model FLOP/s of the decode window over the chips' bf16 peak, in percent.

FLOPs per decoded token, from the configuration's sizes (never from the
program): 2 per weight used (every Mamba layer's in_proj, conv and
out_proj; every shared-block call's attention, MLP, adapter and linear,
each call counted; the tied head), plus attention at the session's
context (4 x context x heads x head size per call: scores and values) and
the SSM at its state (2 x 2 x heads x head size x state per layer: the
state update's outer product and the read-out). Tokens are the program's
``xla.decode.tokens`` counter; the context is the mean over the window's
steps (FLOPs are affine in it)."""


def flops_per_token(c: dict, context: float) -> float:
    d, ff, r = c["hidden_size"], c["intermediate_size"], c["adapter_rank"]
    di = c["mamba_expand"] * d
    gn = c["mamba_ngroups"] * c["mamba_d_state"]
    h, n = c["n_mamba_heads"], c["mamba_d_state"]
    att = c["num_attention_heads"] * c["attention_head_dim"]
    conv = di + 2 * gn
    mamba = d * (di + conv + h) + c["mamba_d_conv"] * conv + di * d
    call = 3 * 2 * d * att + att * d + 3 * d * ff + d * r + r * 2 * ff + d * d
    weights = (c["num_hidden_layers"] * mamba
               + len(c["hybrid_layer_ids"]) * call + c["vocab_size"] * d)
    ssm = c["num_hidden_layers"] * 2 * h * c["mamba_headdim"] * n
    attn = len(c["hybrid_layer_ids"]) * 4 * context * att
    return 2.0 * (weights + ssm) + attn


def read(run):
    trace, st = run.stats.get("obs"), run.stats
    if trace is None or not run.peaks or not st.get("contexts"):
        return None
    tokens = trace.metrics.get("xla.decode.tokens", {}).get("value")
    if not tokens or not st.get("elapsed_s"):
        return None
    ctx = sum(st["contexts"]) / len(st["contexts"])
    rate = tokens * flops_per_token(run.config, ctx) / st["elapsed_s"]
    return 100.0 * rate / (run.cell.chips * run.peaks["bf16_flops_per_s"])
