"""Closed-loop greedy decode of a language model through the XLA target.

Traffic keys: ``batch`` sessions decoded together, each with a ``prompt``
of ids drawn uniformly from the seed and a cache of ``context`` positions;
``prefill_batch`` sessions per prefill call in set-up; ``extend`` the ids
per call of the stretch that places a session in its life (below);
``kept_steps`` steps drawn from the seed among the first ``kept_within``,
whose logits are kept for the check beside those of the last step.

The system is built as a user of the toolchain builds it:
``Creator.build`` then ``translate(target="xla", kind="prefill")`` and
``kind="decode"`` (one token per session per step, and ``extend`` tokens of
one session per step for set-up). Set-up draws the weights on the device,
prefills every prompt, snapshots each session's state, and warms every
program the window runs (decode, greedy pick, restore). Then each session
is placed at a point of its life drawn from the seed: a stretch of ids
drawn from the seed, a multiple of ``extend`` long, is prefilled after its
prompt through the cache, so the sessions' positions spread over
[prompt, context) and the window samples whole session lifetimes. The
window is a closed loop with no offered rate: each step decodes one token
of every session, greedy on the device, one step in flight ahead of the
fetch of the previous step's ids. A session whose cache is full goes back
to its snapshot (the state after its prompt). ``windows_per_s`` counts one
decoded token of one session back on the host as one window.

After the window, with the program freed, the plain reference is run
teacher-forced over each session's ids (its prompt, its stretch, and the
ids it was given in that life), one session at a time, and the kept logits
and the greedy picks are compared with it.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List

import numpy as np

from bench.harness import spec, system

try:                                     # the decode counters this kind reads
    from repro.core.target import XLADeployment
    if "step_tokens" not in XLADeployment.__dataclass_fields__:
        raise ImportError("XLADeployment takes no decode step of several "
                          "tokens")
except ImportError as e:
    raise spec.SpecError(
        f"the program cannot run an lm_decode cell ({e})") from e


#: the rtols at which the check records the largest excess (PERF.md)
EXCESS_RTOLS = (0.0, 0.01, 0.02, 0.05)


def model_config(config: dict):
    """The program's ``ModelConfig`` for a published-style configuration."""
    from repro.core.types import ModelConfig, SSMConfig

    c = config
    if c.get("model_type") != "zamba2":
        raise spec.SpecError(f"lm_decode runs zamba2 configurations, not "
                             f"{c.get('model_type')!r}")
    return ModelConfig(
        name=c["name"], family="hybrid", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c["attention_head_dim"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], act=c["hidden_act"],
        rope_theta=float(c["rope_theta"]),
        ssm=SSMConfig(d_state=c["mamba_d_state"], expand=c["mamba_expand"],
                      headdim=c["mamba_headdim"], n_groups=c["mamba_ngroups"],
                      chunk=c["chunk_size"], conv_width=c["mamba_d_conv"]),
        hybrid_layer_ids=tuple(c["hybrid_layer_ids"]),
        num_mem_blocks=c["num_mem_blocks"], adapter_rank=c["adapter_rank"],
        tie_embeddings=bool(c["tie_word_embeddings"]))


def stretch_lengths(rng: np.random.Generator, traffic: dict) -> np.ndarray:
    """Per session, how many ids after its prompt set-up places it: a
    multiple of ``extend`` below ``context - prompt``, stratified over the
    sessions (a seeded permutation of equal strata, each jittered), so
    every run spreads them over the whole life and one always starts in
    the last ``extend`` positions before its cache is full."""
    n, chunk = int(traffic["batch"]), int(traffic["extend"])
    slots = (int(traffic["context"]) - int(traffic["prompt"])) // chunk
    if not 1 <= slots <= n:
        raise spec.SpecError(f"lm_decode: (context - prompt) / extend = "
                             f"{slots} must lie in [1, batch]")
    u = rng.permutation(n) + rng.random(n)
    return np.floor(u * slots / n).astype(np.int64) * chunk


def reference_logits(ref, config, weights, toks, positions, bucket,
                     weight_dtype=None) -> np.ndarray:
    """The reference's logits at ``positions`` of one session's ids
    ``toks``. The ids are padded at the end to a multiple of ``bucket``
    (the reference is causal, so no earlier logit changes) so that few
    lengths compile."""
    padded = np.zeros(-(-len(toks) // bucket) * bucket, np.int32)
    padded[:len(toks)] = toks
    out = ref.forward(config, weights, padded, weight_dtype=weight_dtype)
    return np.asarray(out[np.asarray(positions)])


def _programs():
    """The small jitted helpers around the deployments (built once)."""
    import jax
    import jax.numpy as jnp

    def bcast(mask, a):
        return mask.reshape((-1,) + (1,) * (a.ndim - 1))

    @partial(jax.jit, donate_argnums=(0,))
    def insert(cache, part, start):
        """Write a cache of fewer sessions at batch offset ``start``."""
        def put(dst, src):
            idx = (start,) + (0,) * (dst.ndim - 1)
            return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype),
                                                idx)
        return jax.tree.map(put, cache, part)

    @jax.jit
    def take(cache, s):
        """Session ``s``'s cache (batch 1)."""
        return jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, s, 1, axis=0), cache)

    @jax.jit
    def snapshot(cache):
        """Every session's recurrent state and position (the KV of the
        prompt is never overwritten, so it needs no copy)."""
        return {"layers": jax.tree.map(jnp.copy, cache["layers"]),
                "pos": [jnp.copy(c["pos"]) for c in cache["shared"]]}

    @partial(jax.jit, donate_argnums=(0,))   # ids are still to be fetched
    def restore(cache, snap, ids, first, mask):
        sel = lambda now, then: jnp.where(bcast(mask, now), then, now)
        layers = jax.tree.map(sel, cache["layers"], snap["layers"])
        shared = tuple(dict(c, pos=sel(c["pos"], p))
                       for c, p in zip(cache["shared"], snap["pos"]))
        return {"layers": layers, "shared": shared}, sel(ids, first)

    @partial(jax.jit, static_argnums=(1,))
    def greedy(logits, vocab):
        """The next ids (B, 1) of logits (B, V) over the real vocabulary."""
        return jnp.argmax(logits[:, :vocab], axis=-1,
                          keepdims=True).astype(jnp.int32)

    return insert, take, snapshot, restore, greedy


class Program:
    """The system under test: the zamba2 deployments and their state."""

    def __init__(self, config: dict, traffic: dict, seed: int, ref):
        import jax
        import jax.numpy as jnp

        from repro.core.creator import Creator
        from repro.core.types import ParallelismConfig, ShapeConfig

        self.cfg = cfg = model_config(config)
        dtype = config["weights"]["dtype"]
        par = ParallelismConfig(param_dtype=dtype, compute_dtype=dtype)
        n, self.pb = int(traffic["batch"]), int(traffic["prefill_batch"])
        context, self.chunk = int(traffic["context"]), int(traffic["extend"])
        cr = Creator()
        _, self.prefill = cr.translate(
            cr.build(cfg, ShapeConfig("prefill", "prefill",
                                      int(traffic["prompt"]), self.pb),
                     par=par), target="xla", kind="prefill")
        st = cr.build(cfg, ShapeConfig("decode", "decode", context, n),
                      par=par)
        _, self.decode = cr.translate(st, target="xla", kind="decode")
        _, self.extend = cr.translate(
            cr.build(cfg, ShapeConfig("extend", "decode", context, 1,
                                      step_tokens=self.chunk), par=par),
            target="xla", kind="decode")
        self.params = ref.to_program(ref.make_params(config, seed),
                                     cfg.padded_vocab)
        self.cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                  st.abstract_inputs()["cache"])
        (self._insert, self._take, self._snapshot, self._restore,
         self._greedy) = _programs()
        self.ids = self.first = self.snap = None

    def start(self, prompts: np.ndarray, stretches: List[np.ndarray]):
        """Prefill every session, snapshot it, warm every program of the
        window, then prefill each session's stretch after its prompt.
        Returns the host ids ``(first, now)``: the greedy pick after each
        prompt (where a reset resumes) and after each stretch (the first
        id the window feeds)."""
        import jax.numpy as jnp

        firsts = []
        for lo in range(0, prompts.shape[0], self.pb):
            logits, part = self.prefill(
                self.params, {"tokens": jnp.asarray(prompts[lo:lo + self.pb])})
            self.cache = self._insert(self.cache, part, lo)
            firsts.append(self._greedy(logits, self.cfg.vocab_size))
        self.first = jnp.concatenate(firsts)
        self.ids = jnp.copy(self.first)
        self.snap = self._snapshot(self.cache)
        np.asarray(self.step()[1])                     # warm decode + pick
        self.reset(np.ones(prompts.shape[0], bool))    # warm restore
        first = np.asarray(self.first)[:, 0]
        now = first.copy()
        for s, ids in enumerate(stretches):
            if not len(ids):
                continue
            row = self._take(self.cache, s)
            for lo in range(0, len(ids), self.chunk):
                logits, row = self.extend(
                    self.params, jnp.asarray(ids[None, lo:lo + self.chunk]),
                    row)
            self.cache = self._insert(self.cache, row, s)
            now[s] = np.asarray(self._greedy(logits, self.cfg.vocab_size))[0, 0]
        self.ids = jnp.asarray(now[:, None])
        return first, now

    def step(self):
        """Issue one decode step of every session; returns the device
        arrays ``(logits (B, V), next ids (B, 1))``."""
        logits, self.cache = self.decode(self.params, self.ids, self.cache)
        self.ids = self._greedy(logits, self.cfg.vocab_size)
        return logits, self.ids

    def reset(self, mask: np.ndarray) -> None:
        """Sessions in ``mask`` go back to their snapshot."""
        import jax.numpy as jnp

        self.cache, self.ids = self._restore(self.cache, self.snap, self.ids,
                                             self.first, jnp.asarray(mask))

    def close(self) -> None:
        self.params = self.cache = self.snap = self.ids = self.first = None


class Reference:
    """The plain reference in the program's place (the control): every
    step recomputes each session's whole history, weights rounded to
    ``weight_dtype``."""

    def __init__(self, config: dict, traffic: dict, seed: int, ref,
                 weight_dtype=None):
        self.config, self.ref, self.wd = config, ref, weight_dtype
        self.bucket = int(traffic["prompt"])
        self.weights = ref.make_params(config, seed)

    def _last(self, seqs) -> np.ndarray:
        return np.stack([reference_logits(
            self.ref, self.config, self.weights, q, [len(q) - 1],
            self.bucket, self.wd)[0] for q in seqs])

    def start(self, prompts, stretches):
        self.prompts = prompts
        self.first = self._last(prompts).argmax(-1).astype(np.int32)
        now = self.first.copy()
        seqs = [np.concatenate([p, q]) for p, q in zip(prompts, stretches)]
        placed = [s for s, q in enumerate(stretches) if len(q)]
        if placed:
            now[placed] = self._last([seqs[s] for s in placed]).argmax(-1)
        #: per session, every id fed so far and the one to feed next
        self.seqs = [list(q) + [int(i)] for q, i in zip(seqs, now)]
        return self.first, now

    def step(self):
        logits = self._last(self.seqs)
        ids = logits.argmax(-1).astype(np.int32)
        for q, i in zip(self.seqs, ids):
            q.append(int(i))
        return logits, ids

    def reset(self, mask):
        for s in np.flatnonzero(mask):
            self.seqs[s] = list(self.prompts[s]) + [int(self.first[s])]

    def close(self):
        self.weights = None


def control(make, *, config, ref, seed, traffic, **_):
    """The control: the reference with its weights rounded to float8_e4m3,
    one step below the stated bfloat16, in the program's place."""
    return lambda: Reference(config, traffic, seed, ref,
                             weight_dtype="float8_e4m3fn")


@dataclass
class State:
    system: object
    prompts: np.ndarray
    stretches: List[np.ndarray]
    first: np.ndarray
    now: np.ndarray
    kept_at: set
    #: per session, the position the next step writes, and its life
    at: np.ndarray
    epoch: np.ndarray
    #: per issued step: (each session's life, position written)
    where: List[tuple] = field(default_factory=list)
    #: per fetched step: the next ids on the host
    steps: List[np.ndarray] = field(default_factory=list)
    #: step index -> logits on the host
    logits: Dict[int, np.ndarray] = field(default_factory=dict)
    issued: int = 0


def setup(run) -> State:
    cfg, tr = run.config, run.traffic
    rng = system.rng_for(run.seed, system.TRAFFIC)
    n, prompt = int(tr["batch"]), int(tr["prompt"])
    vocab = int(cfg["vocab_size"])
    prompts = rng.integers(0, vocab, (n, prompt), dtype=np.int32)
    stretches = [rng.integers(0, vocab, int(m), dtype=np.int32)
                 for m in stretch_lengths(rng, tr)]
    kept_at = set(int(i) for i in rng.choice(
        int(tr["kept_within"]), int(tr["kept_steps"]), replace=False))
    make = run.system(partial(Program, cfg, tr, run.seed, run.cell.ref),
                      seed=run.seed, traffic=tr)
    sysm = make()
    first, now = sysm.start(prompts, stretches)
    at = prompt + np.array([len(q) for q in stretches], np.int64)
    run.stats["start_positions"] = at.tolist()
    return State(sysm, prompts, stretches, first, now, kept_at, at,
                 np.zeros(n, np.int64))


def _loop(run, st: State):
    """The timed loop; returns the seconds from the first issue to the last
    fetch."""
    tr, spans, sysm = run.traffic, run.spans, st.system
    n, prompt, context = (int(tr[k]) for k in ("batch", "prompt", "context"))
    contexts = run.stats.setdefault("contexts", [])
    run.stats["resets"] = 0
    t0 = time.perf_counter()
    end = t0 + run.seconds

    def issue():
        full = st.at >= context
        if full.any():
            sysm.reset(full)
            st.at[full] = prompt
            st.epoch[full] += 1
            run.stats["resets"] += int(full.sum())
        with spans("bench.call"):
            out = sysm.step()
        st.where.append((st.epoch.copy(), st.at.copy()))
        contexts.append(float(np.mean(st.at)) + 1.0)
        st.at += 1
        st.issued += 1
        return st.issued - 1, out

    pending = issue()
    while True:
        last = time.perf_counter() >= end
        nxt = None if last else issue()
        k, (logits, ids) = pending
        with spans("bench.fetch"):
            host = np.asarray(ids).reshape(n)
            if k in st.kept_at or last:
                st.logits[k] = np.asarray(logits)
        st.steps.append(host)
        if last:
            return time.perf_counter() - t0
        pending = nxt


def window(run, st: State):
    """Returns ``(end-to-end values, kept outputs)``."""
    if run.trace:
        from repro.obs import capture

        with capture("lm_decode") as cap:
            elapsed = _loop(run, st)
        run.stats["obs"] = cap.trace
    else:
        elapsed = _loop(run, st)
    tokens = len(st.steps) * int(run.traffic["batch"])
    run.stats.update(windows=tokens, elapsed_s=elapsed, steps=len(st.steps))
    return {"windows_per_s": tokens / elapsed}, st.logits


def payload(st: State):
    """What the check needs once the program's state is freed."""
    st.system.close()
    return (st.prompts, st.stretches, st.first, st.now, st.where, st.steps,
            st.issued)


def check_outputs(run, pl, kept) -> dict:
    """Every kept step of every session against the reference,
    teacher-forced over the ids that session was fed in that life. Counted
    in ``mismatched_codes``: a logit outside ``atol + rtol * |want|``; a
    greedy pick that is not the argmax of the kept logits; and a pick that
    is not the reference's argmax where the reference's top two differ by
    more than the sum of their tolerances (so the pick is decided)."""
    prompts, stretches, first, now, where, steps, issued = pl
    cfg, ref = run.config, run.cell.ref
    atol, rtol = cfg["tolerance"]["atol"], cfg["tolerance"]["rtol"]
    bucket = int(run.traffic["prompt"])
    weights = ref.make_params(cfg, run.seed)
    #: per rtol, the largest |got - want| - rtol |want| (the atol at which
    #: no logit would be counted): the readings the tolerance is set from
    excess = {r: -np.inf for r in EXCESS_RTOLS}
    bad_logits = bad_picks = 0
    for s in range(prompts.shape[0]):
        lives: Dict[int, list] = {}
        for k in sorted(kept):
            lives.setdefault(int(where[k][0][s]), []).append(
                (k, int(where[k][1][s])))
        for e, ks in lives.items():
            lead = (np.concatenate([prompts[s], stretches[s], now[s:s + 1]])
                    if e == 0 else np.append(prompts[s], first[s]))
            fed = [steps[k][s] for k in range(len(steps))
                   if where[k][0][s] == e]
            toks = np.concatenate([lead, np.asarray(fed, lead.dtype)])
            top = max(pos for _, pos in ks)
            wants = reference_logits(ref, cfg, weights, toks[:top + 1],
                                     [pos for _, pos in ks], bucket)
            for (k, _), want in zip(ks, wants):
                got = kept[k][s, :want.shape[0]]
                err = np.abs(got - want)
                lim = atol + rtol * np.abs(want)
                bad_logits += int(np.count_nonzero(~(err <= lim)))
                for r in excess:
                    excess[r] = max(excess[r],
                                    float(np.max(err - r * np.abs(want))))
                pick = int(steps[k][s])
                bad_picks += int(pick != int(np.argmax(got)))
                two = np.argsort(want)[-2:]
                if want[two[1]] - want[two[0]] > lim[two[1]] + lim[two[0]]:
                    bad_picks += int(pick != int(two[1]))
    run.stats.update(logit_excess=excess, bad_logits=bad_logits,
                     bad_picks=bad_picks)
    return {"mismatched_codes": bad_logits + bad_picks,
            "missing_answers": issued - len(steps),
            "attempted": len(steps) * prompts.shape[0], "failed": 0}
