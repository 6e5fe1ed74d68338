"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the requests it served, drawn from the seed and always holding the longest,
is run through the reference: each prompt with the tokens served so far
(a request still live at the close counts with what it had), in one
teacher-forced pass.  At every served position the reference's logits give
the *gap* of the served token: how far its logit lies below the
reference's best.  Where the cell's check file asks for ``first_tokens``,
the first token of that many more requests (the dense head after the
prefill) is read the same way, from a pass over each prompt alone.  The
check file names the numbers compared and their limits (the widest gap,
the rms gap, the mean gap of first tokens, ...).  Served tokens are
greedy, so a sound program's gaps come only from rounding.

A control puts the reference, computed in a lower precision (int8 or fp8;
weights scaled per output channel, activations per row), in the program's
place: at the same positions it picks its own argmax, whose gap is read
under the f32 reference.  ``gaps`` and ``first_gaps`` read the controls
the check file names too, when asked.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import sketch as sketch_ref

MIN_TOKENS = 4000     # served tokens the sample holds at least
MAX_REQUESTS = 8      # ... unless it has this many requests
ROWS = 8              # reference batch: the sample padded to this many rows
T_STEP = 1024         # ... and to a multiple of this many positions
BLOCK = 256           # served positions per reference head call
FIRST_ROWS = 32       # prompts per reference pass for first tokens
FIRST_STEP = 256      # ... padded to a multiple of this many positions


def served(records) -> List:
    """Requests with at least one served token, in the order they came."""
    return sorted((r for r in records if r.n > 0), key=lambda r: r.idx)


def pick(records, seed: int) -> List:
    """The longest served request (prompt and tokens served so far,
    finished or not), then others in an order drawn from the seed, until
    ``MIN_TOKENS`` served tokens or ``MAX_REQUESTS``."""
    done = served(records)
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + r.n, -r.idx))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([abs(int(seed)), 7]).permutation(len(rest))
    out, n = [longest], longest.n
    for i in order:
        if n >= MIN_TOKENS or len(out) >= min(MAX_REQUESTS, ROWS):
            break
        out.append(rest[i])
        n += rest[i].n
    return out


def pick_first(records, seed: int, n: int) -> List:
    """``n`` served requests drawn from the seed (all, if fewer), whose
    first tokens are compared."""
    done = served(records)
    order = np.random.default_rng([abs(int(seed)), 11]).permutation(
        len(done))
    return [done[i] for i in sorted(order[:n])]


def _freeze(cfg):
    """A hashable copy of a configuration (for jit's static arguments)."""
    if isinstance(cfg, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in cfg.items()))
    if isinstance(cfg, list):
        return tuple(_freeze(v) for v in cfg)
    return cfg


def _thaw(frozen):
    if isinstance(frozen, tuple) and all(isinstance(k, tuple) and len(k) == 2
                                         and isinstance(k[0], str)
                                         for k in frozen) and frozen:
        return {k: _thaw(v) for k, v in frozen}
    return frozen


@partial(jax.jit, static_argnames=("ref", "cfg_key", "quant"))
def _block_logits(params, head_arrays, hid, seq, pos, first, *, ref,
                  cfg_key, quant):
    """Reference logits at BLOCK served positions: the dense head for a
    first token (prefill emits it), the sketch head after it when the
    configuration serves one."""
    cfg = _thaw(cfg_key)
    h = hid[seq, pos]
    dense = ref.dense_logits(params, h, cfg, quant)
    if cfg["head"]["kind"] == "dense":
        return dense
    sk = sketch_ref.logits(head_arrays, h, cfg["head"], block=BLOCK)
    return jnp.where(first[:, None], dense, sk)


@jax.jit
def _gap(logits, tok):
    best = jnp.max(logits, -1)
    mine = jnp.take_along_axis(logits, tok[:, None], -1)[:, 0]
    return best - mine


def _stats(g, dense, prefix=""):
    g = np.asarray(g, np.float64)
    dense = np.asarray(dense, bool)
    finite = bool(np.isfinite(g).all())
    out = {f"{prefix}max_gap": float(g.max()) if finite else float("inf"),
           f"{prefix}mean_gap": float(g.mean()) if finite else float("inf"),
           f"{prefix}rms_gap": float(np.sqrt((g * g).mean()))
           if finite else float("inf"),
           f"{prefix}miss_share": float((g > 0).mean())}
    for name, sel in (("dense", dense), ("sketch", ~dense)):
        if sel.any():
            out[f"{prefix}{name}_max_gap"] = float(g[sel].max())
            out[f"{prefix}{name}_mean_gap"] = float(g[sel].mean())
    return out


def gaps(ref, params, cfg, head_arrays, prompts, served,
         controls=()) -> Dict[str, float]:
    """Gaps of the served tokens (and, for each of ``controls``, "int8" or
    "fp8", of that reference's own picks, under ``<control>_<number>``)
    at every served position of the sample.

    Every shape is fixed: the sample is padded to ``ROWS`` sequences and a
    multiple of ``T_STEP`` positions (after each sequence; the model is
    causal), and its served positions to blocks of ``BLOCK``, so that runs
    share the reference's compiled programs."""
    t0 = time.perf_counter()
    lens = [len(p) + len(s) - 1 for p, s in zip(prompts, served)]
    tokens = np.zeros((ROWS, -(-max(lens) // T_STEP) * T_STEP), np.int32)
    seq, pos, tok, first = [], [], [], []
    for i, (p, s) in enumerate(zip(prompts, served)):
        tokens[i, :lens[i]] = np.concatenate([p, s[:-1]])
        seq += [i] * len(s)
        pos += list(range(len(p) - 1, len(p) - 1 + len(s)))
        tok += list(s)
        first += [True] + [False] * (len(s) - 1)
    n = len(tok)
    pad = -n % BLOCK
    seq, pos, tok = (np.asarray(a + [0] * pad, np.int32)
                     for a in (seq, pos, tok))
    first = np.asarray(first + [True] * pad)
    dense = first | (cfg["head"]["kind"] == "dense")
    key = _freeze(cfg)
    hid = ref.hidden(params, tokens, cfg)
    hid_q = {c: ref.hidden(params, tokens, cfg, c) for c in controls}
    t1 = time.perf_counter()
    g, gq = [], {c: [] for c in controls}
    for lo in range(0, n + pad, BLOCK):
        sl = slice(lo, lo + BLOCK)
        args = (jnp.asarray(seq[sl]), jnp.asarray(pos[sl]),
                jnp.asarray(first[sl]))
        lg = _block_logits(params, head_arrays, hid, *args, ref=ref,
                           cfg_key=key, quant=None)
        g.append(np.asarray(_gap(lg, jnp.asarray(tok[sl]))))
        for c in controls:
            lq = _block_logits(params, head_arrays, hid_q[c], *args,
                               ref=ref, cfg_key=key, quant=c)
            gq[c].append(np.asarray(_gap(lg, jnp.argmax(lq, -1))))
    out = {"tokens": n, "requests": len(prompts),
           "hidden_s": t1 - t0, "heads_s": time.perf_counter() - t1}
    out.update(_stats(np.concatenate(g)[:n], dense[:n]))
    for c in controls:
        out.update(_stats(np.concatenate(gq[c])[:n], dense[:n], f"{c}_"))
    return out


@partial(jax.jit, static_argnames=("ref", "cfg_key", "quant"))
def _last_logits(params, hid, last, *, ref, cfg_key, quant):
    """Dense logits at each row's last prompt position."""
    h = jnp.take_along_axis(hid, last[:, None, None], axis=1)[:, 0]
    return ref.dense_logits(params, h, _thaw(cfg_key), quant)


def first_gaps(ref, params, cfg, prompts, firsts,
               controls=()) -> Dict[str, float]:
    """Gaps of first tokens (the dense head after the prefill), each from
    a pass over its prompt alone: ``first_max_gap``, ``first_mean_gap``
    and ``first_miss_share``, and the same for each of ``controls`` under
    ``<control>_first_...``.

    Prompts go through the reference ``FIRST_ROWS`` at a time, shortest
    first, each group padded to a multiple of ``FIRST_STEP`` positions
    (after each prompt; the model is causal) and to ``FIRST_ROWS`` rows."""
    key = _freeze(cfg)
    order = np.argsort([len(p) for p in prompts], kind="stable")
    g, gq = [], {c: [] for c in controls}
    for lo in range(0, len(order), FIRST_ROWS):
        rows = order[lo:lo + FIRST_ROWS]
        width = -(-max(len(prompts[i]) for i in rows) // FIRST_STEP)
        tokens = np.zeros((FIRST_ROWS, width * FIRST_STEP), np.int32)
        last = np.zeros(FIRST_ROWS, np.int32)
        tok = np.zeros(FIRST_ROWS, np.int32)
        for j, i in enumerate(rows):
            tokens[j, :len(prompts[i])] = prompts[i]
            last[j], tok[j] = len(prompts[i]) - 1, firsts[i]
        last = jnp.asarray(last)
        lg = _last_logits(params, ref.hidden(params, tokens, cfg), last,
                          ref=ref, cfg_key=key, quant=None)
        g.append(np.asarray(_gap(lg, jnp.asarray(tok)))[:len(rows)])
        for c in controls:
            lq = _last_logits(params, ref.hidden(params, tokens, cfg, c),
                              last, ref=ref, cfg_key=key, quant=c)
            gq[c].append(np.asarray(_gap(lg, jnp.argmax(lq, -1)))
                         [:len(rows)])
    out = {"first_tokens": len(prompts)}
    for prefix, vals in [("", g)] + [(f"{c}_", gq[c]) for c in controls]:
        v = np.concatenate(vals).astype(np.float64)
        finite = bool(np.isfinite(v).all())
        out[f"{prefix}first_max_gap"] = (float(v.max()) if finite
                                         else float("inf"))
        out[f"{prefix}first_mean_gap"] = (float(v.mean()) if finite
                                          else float("inf"))
        out[f"{prefix}first_miss_share"] = float((v > 0).mean())
    return out
