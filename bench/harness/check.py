"""How ``correct`` is decided: the served tokens against a plain reference.

After the window has closed and the program's state is freed, a sample of
the requests it finished is drawn from the seed, with the longest request
in it, until it holds the cell's number of served tokens. The family's
float32 reference (``bench/families/<family>.py``) runs once over each
prompt with its served tokens, layer by layer with weights it draws
itself, and reads the logits at every served position. A token's gap is
how far, in logits, it falls short of the token the reference would have
chosen. Two numbers come of it: ``logit_gap``, the widest gap, and
``mean_logit_gap``, the mean over the sample's served tokens. A cell's
limits file (``bench/limits/<cell>.json``) names the numbers it compares
and the sample's size: enough served tokens that the number compared
separates the program from the control. Under greedy decoding a gap shows
only where the reference's two best tokens lie closer than the error, so
a sample needs enough such near ties on both sides.

Under greedy decoding that is the reference's largest logit less the
served token's. A sampled token is the argmax of the logits plus Gumbel
noise that the serving loop draws from ``(sampling seed, request id,
position)``; the reference draws the same noise, so a sampled token has a
gap in the same sense: the reference's best perturbed score, over the
tokens the reference ranks in the first half of the top-k, less the served
token's; plus how far the served token's logit falls below the reference's
k-th, where the served token lies outside the top-k altogether.

The control is the same reference with every matmul in float8 (the step
below the configuration's bfloat16), put in the program's place: it
chooses its own token at each position of the same sequences, its gap is
read in the same way, and ``judge`` holds it to the same limits. It has to
come out as not correct.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import weights

BATCH = 8            # sequences per pass of the reference


def judge(readings: dict, limits: dict) -> tuple[dict, bool]:
    """Each number the cell's limits name, beside its limit, and whether
    every one is within it. A reading the limits do not name is not
    compared."""
    checks = {k: {"value": readings[k], "limit": lim}
              for k, lim in limits.items()}
    return checks, all(v["value"] <= v["limit"] for v in checks.values())


def sample(planned, completions: dict, seed: int, *, min_tokens: int,
           max_requests: int) -> list:
    """The longest finished request and others drawn from the seed, until
    ``min_tokens`` served tokens or ``max_requests`` requests."""
    done = [p for p in planned if p.rid in completions]
    if not done:
        return []
    longest = max(done, key=lambda p: (len(p.prompt) + p.max_new, -p.rid))
    rng = np.random.default_rng([seed, 0xC4EC])
    rest = [done[i] for i in rng.permutation(len(done)) if done[i] is not longest]
    picked, served = [longest], longest.max_new
    for p in rest:
        if served >= min_tokens or len(picked) >= max_requests:
            break
        picked.append(p)
        served += p.max_new
    return picked


def request_key(sampling_seed: int, rid: int):
    """The serving loop's key for a request: the rid folded into the base
    key in two 32-bit halves."""
    k = jax.random.fold_in(jax.random.PRNGKey(sampling_seed), rid & 0xFFFFFFFF)
    return jax.random.fold_in(k, (rid >> 32) & 0xFFFFFFFF)


class Reference:
    """The family's reference over a fixed batch, sequence length and
    number of served positions, so that one set of compiled programs
    serves every run of a cell."""

    def __init__(self, family, c: dict, layout: dict, weight_seed: int,
                 batch: int, length: int, n_pos: int, vocab: int):
        self.family, self.c = family, c
        self.batch, self.length, self.vocab = batch, length, vocab
        self.n_pos = n_pos
        self.n_layers = next(s[0][0] for s in layout.values() if s[3])
        self.layer_weights = weights.layer_fn(layout, weight_seed)
        self.g = weights.globals_f32(layout, weight_seed)
        self._embed = jax.jit(functools.partial(family.embed, c))
        self._layer = {
            m: jax.jit(functools.partial(family.layer, c, mode=m))
            for m in ("f32", "fp8")
        }
        self._head = {
            m: jax.jit(_gather_head(family, c, m)) for m in ("f32", "fp8")
        }

    def logits(self, tokens, idx, mode: str):
        """Logits (B, N, V) at positions ``idx`` (B, N) of ``tokens`` (B, T)."""
        with jax.default_matmul_precision("highest"):
            x = self._embed(self.g, tokens)
            for l in range(self.n_layers):
                x = self._layer[mode](self.layer_weights(l), x)
            return self._head[mode](self.g, x, idx)


def _gather_head(family, c, mode):
    def fn(g, x, idx):
        h = jnp.take_along_axis(x, idx[..., None], axis=1)
        return family.head(c, g, h, mode=mode)
    return fn


def gumbel(sampling_seed: int, rids, n_pos: int, vocab: int, dtype: str):
    """(B, n_pos, V) float32: the noise the loop adds at each position of
    each request, drawn in the served dtype as the loop draws it."""
    keys = jnp.stack([request_key(sampling_seed, int(r)) for r in rids])
    return _draw_gumbel(keys, n_pos=n_pos, vocab=vocab, dtype=dtype)


@functools.partial(jax.jit, static_argnames=("n_pos", "vocab", "dtype"))
def _draw_gumbel(keys, *, n_pos: int, vocab: int, dtype: str):
    def one(k, i):
        return jax.random.gumbel(
            jax.random.fold_in(k, i), (vocab,), jnp.dtype(dtype)
        ).astype(jnp.float32)

    pos = jnp.arange(n_pos)
    return jax.vmap(lambda k: jax.vmap(lambda i: one(k, i))(pos))(keys)


@functools.partial(jax.jit, static_argnames=("temperature", "top_k"))
def token_gaps(ref, tokens, valid, noise, *, temperature: float, top_k: int):
    """(B, N) gaps of ``tokens`` against the reference logits ``ref``."""
    take = lambda a: jnp.take_along_axis(a, tokens[..., None], -1)[..., 0]
    if temperature <= 0.0:
        gap = ref.max(-1) - take(ref)
    else:
        score = ref + temperature * noise
        gap_core = score
        below_k = jnp.zeros(tokens.shape, jnp.float32)
        if top_k > 0:
            core = jax.lax.top_k(ref, max(1, top_k // 2))[0][..., -1:]
            gap_core = jnp.where(ref >= core, score, -jnp.inf)
            kth = jax.lax.top_k(ref, top_k)[0][..., -1]
            below_k = jnp.maximum(kth - take(ref), 0.0)
        gap = jnp.maximum(gap_core.max(-1) - take(score), 0.0) + below_k
    return jnp.where(valid, gap, 0.0)


@functools.partial(jax.jit, static_argnames=("temperature", "top_k"))
def choose(logits, noise, *, temperature: float, top_k: int):
    """The token the serving loop's rule picks from ``logits``."""
    if temperature <= 0.0:
        return jnp.argmax(logits, -1).astype(jnp.int32)
    lg = logits
    if top_k > 0:
        kth = jax.lax.top_k(lg, top_k)[0][..., -1:]
        lg = jnp.where(lg < kth, -jnp.inf, lg)
    return jnp.argmax(lg + temperature * noise, -1).astype(jnp.int32)


def batch_of(seqs, length: int, batch: int, n: int):
    """Pad the (prompt, served) pairs into (B, T) tokens, (B, N) served
    tokens, their positions and their mask."""
    tokens = np.zeros((batch, length), np.int32)
    served = np.zeros((batch, n), np.int32)
    idx = np.zeros((batch, n), np.int32)
    valid = np.zeros((batch, n), bool)
    for b, (prompt, out) in enumerate(seqs):
        seq = np.concatenate([prompt, out])
        tokens[b, : len(seq)] = seq
        served[b, : len(out)] = out
        idx[b, : len(out)] = len(prompt) - 1 + np.arange(len(out))
        valid[b, : len(out)] = True
    return tokens, served, idx, valid


def gaps(ref: Reference, seqs, rids, *, sampling: dict, sampling_seed: int,
         dtype: str, control: bool = False) -> dict:
    """The served tokens' gaps: the widest per request, where it lies, and
    the mean over every served token; with ``control``, the widest and the
    mean for the tokens the float8 control chooses. The reference takes
    the sequences ``ref.batch`` at a time."""
    temp, top_k = float(sampling["temperature"]), int(sampling["top_k"])
    rows: dict = {"served": [], "control": []}
    n_valid = 0
    for i in range(0, len(seqs), ref.batch):
        part, part_rids = seqs[i: i + ref.batch], list(rids[i: i + ref.batch])
        tokens, served, idx, valid = batch_of(part, ref.length, ref.batch,
                                              ref.n_pos)
        rids_b = part_rids + [part_rids[0]] * (ref.batch - len(part))
        noise = (
            gumbel(sampling_seed, rids_b, served.shape[1], ref.vocab, dtype)
            if temp > 0 else jnp.zeros((), jnp.float32)
        )
        want = ref.logits(tokens, idx, "f32")
        rows["served"].append(np.asarray(token_gaps(
            want, served, valid, noise, temperature=temp, top_k=top_k))[: len(part)])
        if control:
            picked = choose(ref.logits(tokens, idx, "fp8"), noise,
                            temperature=temp, top_k=top_k)
            rows["control"].append(np.asarray(token_gaps(
                want, picked, valid, noise, temperature=temp, top_k=top_k))[: len(part)])
        n_valid += int(valid[: len(part)].sum())
    g = np.concatenate(rows["served"])
    out = {"served": g.max(-1), "served_at": g.argmax(-1),
           "served_mean": float(g.sum() / n_valid)}
    if control:
        gc = np.concatenate(rows["control"])
        out["control"] = gc.max(-1)
        out["control_mean"] = float(gc.sum() / n_valid)
    return out
