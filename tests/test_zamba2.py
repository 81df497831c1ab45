"""Zamba2 through the normal path against its plain reference (CPU, smoke
size, seeded random weights): ``Creator.build → translate(target="xla")``
for prefill and decode, the benchmark's plain reference
``bench/configs/zamba2_7b_ref.py`` for the full forward."""
from __future__ import annotations

import dataclasses
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import zamba2_7b
from repro.core.creator import Creator
from repro.core.types import ShapeConfig
from repro.model.transformer import pad_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load_reference():
    path = ROOT / "bench" / "configs" / "zamba2_7b_ref.py"
    spec = importlib.util.spec_from_file_location("_zamba2_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def published_config(cfg) -> dict:
    """The published config keys of a ``repro`` zamba2 ``ModelConfig``."""
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "attention_head_dim": cfg.hd, "intermediate_size": cfg.d_ff,
        "mamba_expand": s.expand, "n_mamba_heads": d_inner // s.headdim,
        "mamba_headdim": s.headdim, "mamba_d_state": s.d_state,
        "mamba_ngroups": s.n_groups, "mamba_d_conv": s.conv_width,
        "vocab_size": cfg.vocab_size, "adapter_rank": cfg.adapter_rank,
        "num_mem_blocks": cfg.num_mem_blocks,
        "hybrid_layer_ids": list(cfg.hybrid_layer_ids),
        "num_hidden_layers": cfg.n_layers, "rms_norm_eps": 1e-5,
        "rope_theta": cfg.rope_theta}


ref = _load_reference()
CFG = zamba2_7b.smoke()
#: unit-scale weights at the smoke width (std 1/sqrt(d)): at the published
#: 0.02 the shared block's term is too small to tell the calls apart
PUB = dict(published_config(CFG), initializer_range=CFG.d_model ** -0.5)
S, B, EXTRA = 12, 2, 4
#: program (f32 compute, chunked SSD, cached decode) against the reference
#: (sequential recurrence, full forward): the same f32 arithmetic summed in
#: another order, so agreement to f32 rounding over 7 layers
TOL = 2e-4


@pytest.fixture(scope="module")
def weights():
    return ref.make_params(PUB, seed=2 ** 31 + 11, dtype=jnp.float32)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(5), (B, S + EXTRA), 0,
                              CFG.vocab_size)


def _deployments(par_f32):
    cr = Creator()
    _, pre = cr.translate(
        cr.build(CFG, ShapeConfig("p", "prefill", S, B), par=par_f32),
        target="xla", kind="prefill")
    _, dec = cr.translate(
        cr.build(CFG, ShapeConfig("d", "decode", S + EXTRA, B), par=par_f32),
        target="xla", kind="decode")
    return pre, dec


def _reference(w, toks):
    return np.stack([np.asarray(ref.forward(PUB, w, t)) for t in toks])


def _program(par_f32, w, toks):
    """Prefill the first S ids, then decode the rest through the cache:
    logits (B, EXTRA + 1, V) at positions S-1 .. S+EXTRA-1."""
    pre, dec = _deployments(par_f32)
    p = ref.to_program(w, CFG.padded_vocab)
    logits, cache = pre(p, {"tokens": toks[:, :S]})
    cache = pad_cache(cache, S + EXTRA)
    out = [logits]
    for t in range(EXTRA):
        logits, cache = dec(p, toks[:, S + t:S + t + 1], cache)
        out.append(logits)
    return np.stack([np.asarray(o) for o in out], axis=1)[..., :CFG.vocab_size]


def test_config_is_the_published_one():
    """Zyphra/Zamba2-7B-Instruct config.json, key by key."""
    c = zamba2_7b.config()
    pub = published_config(c)
    want = {
        "hidden_size": 3584, "num_attention_heads": 32,
        "attention_head_dim": 224, "intermediate_size": 14336,
        "mamba_expand": 2, "n_mamba_heads": 112, "mamba_headdim": 64,
        "mamba_d_state": 64, "mamba_ngroups": 2, "mamba_d_conv": 4,
        "vocab_size": 32000, "adapter_rank": 128, "num_mem_blocks": 2,
        "hybrid_layer_ids": [6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71,
                             77],
        "num_hidden_layers": 81, "rms_norm_eps": 1e-5, "rope_theta": 10000}
    assert pub == want
    assert c.n_kv_heads == 32 and c.act == "gelu" and c.tie_embeddings
    assert c.ssm.chunk == 256          # chunk_size
    assert not hasattr(c, "shared_attn_every")


def test_smoke_preset_covers_every_part():
    c = zamba2_7b.smoke()
    gaps = np.diff((-1,) + c.hybrid_layer_ids)
    assert c.num_mem_blocks == 2 and len(c.hybrid_layer_ids) >= 3
    assert len(set(gaps)) > 1                       # irregular positions
    assert c.ssm.n_groups == 2 and c.adapter_rank > 0


def test_prefill_logits_match_reference(par_f32, weights, tokens):
    pre, _ = _deployments(par_f32)
    logits, _ = pre(ref.to_program(weights, CFG.padded_vocab),
                    {"tokens": tokens[:, :S]})
    want = _reference(weights, tokens[:, :S])[:, -1]
    np.testing.assert_allclose(np.asarray(logits)[:, :CFG.vocab_size], want,
                               rtol=TOL, atol=TOL)


def test_prefill_then_decode_matches_reference(par_f32, weights, tokens):
    got = _program(par_f32, weights, tokens)
    want = _reference(weights, tokens)[:, S - 1:]
    np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)


def test_a_step_of_several_tokens_continues_each_row_from_its_position(
        par_f32, weights, tokens):
    """A decode step of ``step_tokens`` > 1 per row (a prefill continued
    through the cache), with the rows at different positions, gives the
    reference's logits, counts its tokens, and leaves a cache the one-token
    step goes on from."""
    from repro.obs import capture

    cr = Creator()
    lens, T, N = (8, 4), 4, S + EXTRA     # rows prefilled to 8 and 4 ids
    p = ref.to_program(weights, CFG.padded_vocab)
    caches = []
    for b, n in enumerate(lens):
        _, pre = cr.translate(cr.build(CFG, ShapeConfig("p", "prefill", n, 1),
                                       par=par_f32),
                              target="xla", kind="prefill")
        caches.append(pad_cache(pre(p, {"tokens": tokens[b:b + 1, :n]})[1],
                                N))
    cache = jax.tree.map(lambda *a: jnp.concatenate(a), *caches)

    def step(t):
        return cr.translate(
            cr.build(CFG, ShapeConfig("d", "decode", N, B, step_tokens=t),
                     par=par_f32), target="xla", kind="decode")[1]

    ext, dec = step(T), step(1)
    with capture() as cap:
        got, cache = ext(p, jnp.stack([tokens[b, n:n + T]
                                       for b, n in enumerate(lens)]), cache)
    assert cap.trace.metrics["xla.decode.tokens"]["value"] == B * T
    nxt, _ = dec(p, jnp.stack([tokens[b, n + T:n + T + 1]
                               for b, n in enumerate(lens)]), cache)
    for b, n in enumerate(lens):
        want = _reference(weights, tokens[b:b + 1, :n + T + 1])[0]
        np.testing.assert_allclose(np.asarray(got)[b, :CFG.vocab_size],
                                   want[n + T - 1], rtol=TOL, atol=TOL)
        np.testing.assert_allclose(np.asarray(nxt)[b, :CFG.vocab_size],
                                   want[n + T], rtol=TOL, atol=TOL)


def _swap(tree, i, j):
    def sw(a):
        a = np.array(a)
        a[[i, j]] = a[[j, i]]
        return jnp.asarray(a)
    return jax.tree.map(sw, tree)


def test_blocks_alternate_and_each_call_has_its_adapter(par_f32, weights,
                                                        tokens):
    """Calls 0 and 2 both run block A with adapters of their own; swapping
    their adapters, or blocks A and B, changes the output, and the program
    follows the reference through each swap."""
    base = _program(par_f32, weights, tokens)
    for key in ("adapters", "blocks"):
        i, j = (0, 2) if key == "adapters" else (0, 1)
        w = dict(weights, **{key: _swap(weights[key], i, j)})
        got = _program(par_f32, w, tokens)
        assert np.max(np.abs(got - base)) > 100 * TOL, key
        np.testing.assert_allclose(got, _reference(w, tokens)[:, S - 1:],
                                   rtol=TOL, atol=TOL)


def test_heads_read_their_own_group(par_f32, weights, tokens):
    """G = 2: a reference that gives every head group 0's B and C (the
    one-group reading) disagrees with the program."""
    dm = ref.dims(PUB)
    di, gn = dm.d_inner, dm.n
    ip = np.array(weights["mamba"]["in_proj"])
    cw = np.array(weights["mamba"]["conv_w"])
    cb = np.array(weights["mamba"]["conv_b"])
    b0, c0 = 2 * di, 2 * di + gn * dm.groups      # B's, then C's columns
    ip[..., b0 + gn:b0 + 2 * gn] = ip[..., b0:b0 + gn]   # group 1 := 0
    ip[..., c0 + gn:c0 + 2 * gn] = ip[..., c0:c0 + gn]
    xb, xc = di, di + 2 * gn
    for a in (cw, cb):
        a[..., xb + gn:xb + 2 * gn] = a[..., xb:xb + gn]
        a[..., xc + gn:xc + 2 * gn] = a[..., xc:xc + gn]
    one_group = dict(weights, mamba=dict(weights["mamba"],
                                         in_proj=jnp.asarray(ip),
                                         conv_w=jnp.asarray(cw),
                                         conv_b=jnp.asarray(cb)))
    got = _program(par_f32, weights, tokens)
    want_g1 = _reference(one_group, tokens)[:, S - 1:]
    assert np.max(np.abs(got - want_g1)) > 100 * TOL


def _hf_model(w):
    """``transformers``' Zamba2ForCausalLM at the smoke size holding ``w``."""
    torch = pytest.importorskip("torch")
    tr = pytest.importorskip("transformers")
    dm = ref.dims(PUB)
    kinds = ["hybrid" if i in dm.ids else "mamba" for i in range(dm.layers)]
    hf = tr.Zamba2Config(
        vocab_size=dm.vocab, hidden_size=dm.d, num_hidden_layers=dm.layers,
        layers_block_type=kinds, mamba_d_state=dm.n, mamba_d_conv=dm.conv,
        mamba_expand=PUB["mamba_expand"], mamba_ngroups=dm.groups,
        n_mamba_heads=dm.m_heads, intermediate_size=dm.ff,
        hidden_act="gelu", num_attention_heads=dm.heads,
        num_mem_blocks=dm.blocks, adapter_rank=dm.rank, use_mem_rope=True,
        rope_theta=dm.theta, rms_norm_eps=dm.eps, chunk_size=256,
        use_shared_attention_adapter=False, tie_word_embeddings=True,
        attn_implementation="eager")
    model = tr.Zamba2ForCausalLM(hf).eval().float()
    for mod in model.modules():       # the fused path's dt limit (0, inf):
        if hasattr(mod, "time_step_min"):   # no clamp at time_step_min
            mod.time_step_min = 0.0
    t = lambda a: torch.tensor(np.array(a, np.float32))
    sd = {"model.embed_tokens.weight": t(w["embed"]),
          "model.final_layernorm.weight": t(w["final_norm"]),
          "lm_head.weight": t(w["embed"])}
    m = w["mamba"]
    for i in range(dm.layers):
        pre = (f"model.layers.{i}.mamba_decoder." if i in dm.ids
               else f"model.layers.{i}.")
        sd[pre + "input_layernorm.weight"] = t(m["norm"][i])
        mx = pre + "mamba."
        sd[mx + "in_proj.weight"] = t(m["in_proj"][i]).T
        sd[mx + "conv1d.weight"] = t(m["conv_w"][i]).T[:, None, :]
        sd[mx + "conv1d.bias"] = t(m["conv_b"][i])
        sd[mx + "dt_bias"] = t(m["dt_bias"][i])
        sd[mx + "A_log"] = t(m["A_log"][i])
        sd[mx + "D"] = t(m["D"][i])
        sd[mx + "norm.weight"] = t(m["gate_norm"][i])
        sd[mx + "out_proj.weight"] = t(m["out_proj"][i]).T
    b = w["blocks"]
    for k, layer in enumerate(dm.ids):
        pre = f"model.layers.{layer}."
        sd[pre + "linear.weight"] = t(w["linear"][k]).T
        blk = pre + "shared_transformer."
        j = k % dm.blocks
        sd[blk + "input_layernorm.weight"] = t(b["input_norm"][j])
        sd[blk + "pre_ff_layernorm.weight"] = t(b["pre_ff_norm"][j])
        for n in ("q", "k", "v", "o"):
            sd[blk + f"self_attn.{n}_proj.weight"] = t(b[n][j]).T
        ff = blk + "feed_forward."
        sd[ff + "gate_up_proj.weight"] = t(b["gate_up"][j]).T
        sd[ff + "down_proj.weight"] = t(b["down"][j]).T
        sd[ff + f"gate_up_proj_adapter_list.{k}.0.weight"] = \
            t(w["adapters"]["a"][k]).T
        sd[ff + f"gate_up_proj_adapter_list.{k}.1.weight"] = \
            t(w["adapters"]["b"][k]).T
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected, unexpected
    # keys left are the same shared tensors under each hybrid layer's name
    assert all(".shared_transformer." in key for key in missing), missing
    return torch, model


def test_reference_matches_transformers(weights, tokens):
    """The reference against ``transformers`` 4.57 ``Zamba2ForCausalLM``
    (pure-torch path, CPU, float32) on the same weights. Tolerance 1e-4:
    both are float32 and agree to 2e-6 here. The torch path takes the SSM as
    one chunk (``chunk_size`` 256 > 16 positions): its multi-chunk fallback
    disagrees with its own one-chunk result (by 1e-2 at ``chunk_size`` 4, at
    one group or two), while the program's chunked SSD agrees with the
    reference's recurrence (the tests above)."""
    torch, model = _hf_model(weights)
    with torch.no_grad():
        got = model(torch.tensor(np.asarray(tokens)), use_cache=False).logits
    want = _reference(weights, tokens)
    np.testing.assert_allclose(got.numpy(), want, rtol=1e-4, atol=1e-4)


def test_program_params_match_the_schema(weights):
    """``to_program`` fills the program's parameter schema exactly."""
    from repro.model.layers import abstract_params
    from repro.model.transformer import param_schema

    want = abstract_params(param_schema(CFG, tp=1))
    got = ref.to_program(weights, CFG.padded_vocab)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape


def test_shared_block_scope_names_the_device_work(par_f32, weights, tokens):
    """``zamba2.shared_block`` and ``mamba2.ssd`` name the HLO a later trace
    reduction attributes device time to."""
    pre, dec = _deployments(par_f32)
    for dep in (pre, dec):
        assert "zamba2.shared_block" in dep.hlo_text
        assert "mamba2.ssd" in dep.hlo_text


def test_hybrid_ids_out_of_order_are_refused():
    with pytest.raises(ValueError, match="hybrid_layer_ids"):
        dataclasses.replace(CFG, hybrid_layer_ids=(3, 1))


def test_deployment_call_spans_and_counters(par_f32, weights, tokens):
    """``XLADeployment`` calls are ``xla.call`` spans and count prefill
    tokens, decode steps and decode tokens."""
    from repro.obs import capture

    pre, dec = _deployments(par_f32)
    p = ref.to_program(weights, CFG.padded_vocab)
    with capture() as cap:
        _, cache = pre(p, {"tokens": tokens[:, :S]})
        cache = pad_cache(cache, S + EXTRA)
        for t in range(2):
            _, cache = dec(p, tokens[:, S + t:S + t + 1], cache)
    m = cap.trace.metrics
    assert m["xla.prefill.tokens"]["value"] == B * S
    assert m["xla.decode.steps"]["value"] == 2
    assert m["xla.decode.tokens"]["value"] == 2 * B
    calls = [s for s in cap.trace.spans if s.name == "xla.call"]
    assert [s.attrs["kind"] for s in calls] == ["prefill", "decode", "decode"]
    assert all(s.attrs["arch"] == CFG.name and s.attrs["batch"] == B
               for s in calls)


def test_dryrun_plan_fits_calls_and_layers():
    """The dry-run's reduced-depth plan weights recover a cost affine in
    the calls and the layers exactly (f = a + calls·c + layers·b)."""
    from repro.launch.dryrun import extrapolation_plan

    for cfg in (zamba2_7b.config(), CFG):
        plan = extrapolation_plan(cfg)
        assert len(plan) == 3
        for c_L, _ in plan:
            assert all(i < c_L.n_layers for i in c_L.hybrid_layer_ids)
        f = lambda c: 5.0 + 7.0 * len(c.hybrid_layer_ids) + 3.0 * c.n_layers
        assert abs(sum(w * f(c_L) for c_L, w in plan) - f(cfg)) < 1e-9
