"""Plain reference of a decoder and of the RL step that the benchmark's
traffic trains, in float32 at ``precision="highest"``.

The decoder is the token table, the configuration's block stack
(``bench/blocks/<block>.py``, its ``layers``), a final RMSNorm and a head
that is the token table where the embeddings are tied. It imports nothing
of the program. Weights are the benchmark's (``bench/weights.py``), stored
in bfloat16 as the configuration states and widened to float32 on use.

The RL step is the traffic's ``algo`` settings written out: leave-one-out
REINFORCE advantages, a PPO-clip surrogate whose old log-probs are the
reference's own (ratio 1), the k3 KL to the reference weights, a mean
over generated tokens, global-norm clipping and Adam. After each update
the weights are stored back in bfloat16, the configuration's dtype.

``precision="fp8"`` is the control: every weight matmul (``mm``) takes
operands rounded to float8_e4m3 with a per-tensor scale, the precision
below bfloat16. Batches are computed in blocks of rows so that logits of
a full vocabulary fit the device.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import change_norms, leaf_norms

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn


def _q8(x):
    """Round to float8_e4m3 with a per-tensor scale; the gradient passes
    straight through, as in training with float8 matmuls."""
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0)
    q = (x / s).astype(F8).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def mm(spec, x, w, precision):
    """A weight matmul in float32, or with float8 operands for "fp8"."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x, w = _q8(x), _q8(w)
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def scan_layers(layer, x, w, keys):
    """``layer(x, p)`` over the leaves ``keys`` of ``w`` stacked on their
    leading axis, each layer recomputed in the backward pass."""
    stack = {k: w[k] for k in keys if k in w}
    step = jax.checkpoint(layer)
    x, _ = jax.lax.scan(lambda x, p: (step(x, p), None), x, stack)
    return x


def logits(block, w, tokens, m, precision="f32"):
    """(B, T) int tokens -> (B, T, V) float32 logits."""
    x = w["embed"].astype(jnp.float32)[tokens]
    x = block.layers(m, precision, x, w)
    x = rms(x, w["final_norm"], m["rms_eps"])
    if m["tie_embeddings"]:
        return mm("btd,vd->btv", x, w["embed"], precision)
    return mm("btd,dv->btv", x, w["head"], precision)


def token_logprobs(block, w, tokens, m, precision="f32"):
    """(B, T) -> (B, T-1): log p(token[t+1] | tokens[:t+1])."""
    lg = logits(block, w, tokens, m, precision)[:, :-1]
    lp = jax.nn.log_softmax(lg, axis=-1)
    return jnp.take_along_axis(lp, tokens[:, 1:, None], axis=-1)[..., 0]


def advantages(rewards):
    r = jnp.asarray(rewards, jnp.float32)
    B = r.shape[0]
    return r - (jnp.sum(r) - r) / (B - 1)


def _rows_loss(w, tokens, gen, adv, ref_lp, denom, block, m, algo,
                precision):
    lp = token_logprobs(block, w, tokens, m, precision)
    mask = gen[:, 1:].astype(jnp.float32)
    ratio = jnp.exp(lp - jax.lax.stop_gradient(lp))
    a = adv[:, None]
    eps = algo["clip_eps"]
    obj = jnp.minimum(ratio * a, jnp.clip(ratio, 1 - eps, 1 + eps) * a)
    d = ref_lp - lp
    kl = jnp.exp(d) - d - 1.0
    loss = (-jnp.sum(obj * mask) + algo["kl_coef"] * jnp.sum(kl * mask))
    return loss / denom, lp


class Reference:
    """The reference trainer for one configuration (its block and sizes
    ``m``) and traffic mix."""

    def __init__(self, block, m: dict, algo: dict, *, precision: str = "f32",
                 rows_per_block: int = 4):
        self.block, self.m, self.algo = block, m, algo
        self.precision = precision
        self.rb = rows_per_block
        self._lp = jax.jit(partial(token_logprobs, block, m=m,
                                   precision=precision))
        self._grad = jax.jit(jax.value_and_grad(
            partial(_rows_loss, block=block, m=m, algo=algo,
                    precision=precision),
            has_aux=True))
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                            donate_argnums=(0,))
        self._f32 = jax.jit(lambda w: jax.tree.map(
            lambda x: x.astype(jnp.float32), w))
        self._adam = jax.jit(self._adam_step, donate_argnums=(2, 3))

    def _row_slices(self, n):
        return [slice(i, min(i + self.rb, n)) for i in range(0, n, self.rb)]

    def logprobs(self, w, tokens):
        tokens = np.asarray(tokens)
        return np.concatenate([np.asarray(self._lp(w, tokens[s]))
                               for s in self._row_slices(len(tokens))])

    def grad(self, w, tokens, gen, adv, ref_lp):
        """Mean loss over the batch's generated tokens and its float32
        gradient, summed over blocks of rows; also the log-probs."""
        gen = np.asarray(gen)
        denom = np.float32(max(gen[:, 1:].sum(), 1))
        w = self._f32(w)                  # the gradient in float32
        g = None
        loss = 0.0
        lps = []
        for s in self._row_slices(len(gen)):
            (l, lp), gb = self._grad(w, tokens[s], gen[s], adv[s],
                                     ref_lp[s], denom)
            g = gb if g is None else self._add(g, gb)
            loss += float(l)
            lps.append(np.asarray(lp))
        return loss, g, np.concatenate(lps)

    def _adam_step(self, t, w, mu, nu, g):
        a = self.algo
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
        scale = (jnp.minimum(1.0, a["max_grad_norm"] / (gn + 1e-9))
                 if a["max_grad_norm"] > 0 else 1.0)
        g = {k: x * scale for k, x in g.items()}
        b1, b2 = a["b1"], a["b2"]
        mu = {k: b1 * mu[k] + (1 - b1) * g[k] for k in g}
        nu = {k: b2 * nu[k] + (1 - b2) * jnp.square(g[k]) for k in g}
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        new = {}
        for k in g:
            p = w[k].astype(jnp.float32)
            u = (mu[k] / c1) / (jnp.sqrt(nu[k] / c2) + a["eps"])
            u = u + a["weight_decay"] * p
            new[k] = (p - a["lr"] * u).astype(w[k].dtype)
        return new, mu, nu, g

    def follow(self, w0, steps):
        """Follow the program's first steps. ``steps``: per step a dict
        of host arrays ``tokens``, ``gen_mask``, ``rewards`` that the
        program's Rollout produced. Returns per step the behaviour and
        reference log-probs (B, T-1), advantages and loss; the norms of
        the first clipped gradient and of the weights' change over all
        steps, per leaf."""
        out = []
        w = w0
        mu = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), w0)
        nu = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), w0)
        g0_norms = None
        for t, s in enumerate(steps, start=1):
            tokens = np.asarray(s["tokens"])
            ref_lp = self.logprobs(w0, tokens)
            adv = np.asarray(advantages(s["rewards"]))
            loss, g, lp = self.grad(w, tokens, s["gen_mask"], adv, ref_lp)
            new_w, mu, nu, gc = self._adam(np.float32(t), w, mu, nu, g)
            if t == 1:
                g0_norms = leaf_norms(self.block, gc)
            del g, gc
            w = new_w
            out.append({"lp": lp, "ref_lp": ref_lp, "adv": adv,
                        "loss": loss})
        del mu, nu
        return out, g0_norms, change_norms(self.block, w, w0)
