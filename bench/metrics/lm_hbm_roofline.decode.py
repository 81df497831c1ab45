"""The least bytes a decode step must move, at the window's step rate, over
the chip's HBM bandwidth, in percent.

Bytes per step, from the configuration's sizes (never from the program):
every distinct weight once at its stored width (``weights.dtype``); the
KV of every hybrid call read up to each session's current position and
the new position written; the SSM state (float32) and the conv window
read and written for every layer; the logits (float32) written. Steps are
the program's ``xla.decode.steps`` counter; the context is the mean over
the window's steps (bytes are affine in it)."""

WIDTH = {"bfloat16": 2, "float16": 2, "float32": 4}


def bytes_per_step(c: dict, sessions: int, context: float) -> float:
    w = WIDTH[c["weights"]["dtype"]]
    d, ff, r = c["hidden_size"], c["intermediate_size"], c["adapter_rank"]
    L, calls = c["num_hidden_layers"], len(c["hybrid_layer_ids"])
    di = c["mamba_expand"] * d
    gn = c["mamba_ngroups"] * c["mamba_d_state"]
    h, p, n = c["n_mamba_heads"], c["mamba_headdim"], c["mamba_d_state"]
    att = c["num_attention_heads"] * c["attention_head_dim"]
    conv = di + 2 * gn
    mamba = (d * (di + conv + h) + (c["mamba_d_conv"] + 1) * conv + di * d
             + 3 * h + di + d)
    block = 3 * 2 * d * att + att * d + 2 * d * ff + ff * d + 3 * d
    weights = (L * mamba + c["num_mem_blocks"] * block
               + calls * (d * r + r * 2 * ff + d * d)
               + c["vocab_size"] * d + d)
    kv = calls * sessions * 2 * att * w * (context + 1)
    state = L * sessions * (2 * h * p * n * 4
                            + 2 * (c["mamba_d_conv"] - 1) * conv * w)
    return weights * w + kv + state + sessions * c["vocab_size"] * 4


def read(run):
    trace, st = run.stats.get("obs"), run.stats
    if trace is None or not run.peaks or not st.get("contexts"):
        return None
    steps = trace.metrics.get("xla.decode.steps", {}).get("value")
    if not steps or not st.get("elapsed_s"):
        return None
    ctx = sum(st["contexts"]) / len(st["contexts"])
    sessions = int(run.traffic["batch"])
    rate = steps * bytes_per_step(run.config, sessions, ctx) / st["elapsed_s"]
    return 100.0 * rate / (run.cell.chips * run.peaks["hbm_bytes_per_s"])
