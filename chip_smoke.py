#!/usr/bin/env python3
"""Serve rwkv6-1.6b at its published width on one TPU chip, and check it.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --four-chips        # the sharded path on 4 chips

One process drives the chip(s).  The model is ``rwkv6-1.6b`` as published
(24 layers, d_model 2048, d_ff 7168, vocab 65536) with random weights made
from ``--seed``.  Sixteen requests (prompts of 128-512 tokens, 32 new tokens
each) go through the continuous-batching engine over 8 slots, at
``decode_chunk`` 1 and 8, with the dense head and with the fused sketch head
(distilled in-process) in f32, int8 and int4 storage.  Checks, all on the
chip:

* the first device is a TPU, and ``REPRO_KERNEL_BACKEND`` is unset;
* the sketch head dispatches to the Pallas kernel, and the compiled decode
  step holds it (``tpu_custom_call``);
* Pallas sketch logits match the ``ref`` backend on the same hiddens;
* engine streams are bitwise the same at ``decode_chunk`` 1 and 8, and on a
  second run of the same requests;
* engine streams are bitwise equal to static ``generate`` where both decode
  the same batch shapes: an engine of 4 slots against ``generate`` in its
  batches of 4;
* every token of the 8-slot engine is a greedy choice of the static model:
  fed its own prompt and stream, one forward pass ranks it among the top
  ``GREEDY_TOP`` of the vocabulary at each position;
* prefill logits of two prompts agree with the same jitted forward on the
  host CPU at ``jax.default_matmul_precision("highest")``, with the weights
  cast to f32 and in the served bf16.

Why a rank for the 8-slot engine: on a TPU one decode step gives the same
rows different last bits at batch 8 than at batch 4, so its greedy
choices part from ``generate``'s (batches of 4) wherever two random-weight
logits sit close.  A wrong cache row or position gives a token from
anywhere in the vocabulary, which the rank check catches.

``--four-chips`` runs only the sharded path: ``LM.with_mesh("1x4")`` with the
sketch count arrays split over ``model``, compared with the same requests
on one device of the same host.

Every line before the last is a report.  The last line is one JSON object,
printed only when every phase ran and every check passed; any failure exits
non-zero.  With no TPU (for example under ``JAX_PLATFORMS=cpu``) the script
exits non-zero before any work.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ARCH = "rwkv6-1.6b"
N_SLOTS = 8
PROMPT_LENS = (128, 256, 384, 512)   # four requests of each, in this order
REQUESTS_PER_LEN = 4
NEW_TOKENS = 32
CHUNKS = (1, 8)
QUANTS = (None, "int8", "int4")
REF_PROMPT_LEN = 64                  # two prompts for the CPU reference

# Prefill logits on the chip against the host CPU at highest precision.
# Each bound sits between the worst of eight sound seeds on a v5e and the
# least of the faults it must catch (PERF.md, Findings).  f32 (weights
# cast up, highest precision on both): sound max error up to 2.1e-3 of
# max |cpu|, top-1 1.0; the WKV state rounded to bf16 reads 4.7e-3.  bf16
# (as served; the chip contracts f32 operands in one bf16 pass where the
# CPU does not): sound rms up to 0.25 and top-1 down to 0.52; one layer
# running its neighbour's weights reads rms 0.54, top-1 0.16.
PREFILL_F32_ERR = 3e-3     # max |chip - cpu| / max |cpu|, f32 weights
PREFILL_F32_TOP1 = 0.99    # share of the 2 x 64 positions with the same argmax
PREFILL_BF16_RMS = 0.37    # rms |chip - cpu| of the bf16 logits (rms ~0.9)
PREFILL_BF16_TOP1 = 0.35
# Pallas vs ref sketch logits, as a share of max |ref|: the same f32 terms
# summed in another order (int4 unpacks to the same integers as int8; its
# bound only leaves margin).
SKETCH_TOL = {None: 1e-5, "int8": 1e-5, "int4": 1e-4}
# Each engine token must rank among this many of the reference logits at its
# position (0-based rank < GREEDY_TOP, ties in its favour); a token drawn at
# random passes with probability GREEDY_TOP / 65536 per position.  Sound
# runs on a v5e ranked at worst 3 (dense) to 8 (sketch heads, 1x4 mesh).
GREEDY_TOP = 16


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


class Report:
    """Printed phase lines plus the pass/fail record of every check."""

    def __init__(self):
        self.failed = []
        self.t0 = time.time()

    def line(self, msg: str) -> None:
        print(f"[{time.time() - self.t0:7.1f}s] {msg}", flush=True)

    def check(self, name: str, ok: bool, detail: str) -> None:
        self.line(f"CHECK {name}: {'PASS' if ok else 'FAIL'} ({detail})")
        if not ok:
            self.failed.append(name)


class CompileCounter:
    """Counts backend compiles, their seconds and persistent-cache hits."""

    def __init__(self):
        from jax import monitoring

        self.n = self.seconds = self.cache_hits = 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.n, self.seconds, self.cache_hits


def _requests(cfg, seed):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, p, dtype=np.int32)
            for p in PROMPT_LENS for _ in range(REQUESTS_PER_LEN)]


def _peak_bytes(jax) -> str:
    stats = [d.memory_stats() or {} for d in jax.devices()]
    return ", ".join(f"{s.get('peak_bytes_in_use', 0) / 2**30:.2f}"
                     for s in stats) + " GiB peak"


def _model(cfg, seed):
    """Random weights from ``seed`` and the jitted backbone forward that
    returns final hiddens (used for every reference below)."""
    import jax

    from repro.models.model import forward, init_model

    params = jax.jit(init_model, static_argnums=1)(jax.random.PRNGKey(seed),
                                                    cfg)
    hidden_fn = jax.jit(lambda p, t: forward(p, t, cfg, remat=False,
                                             return_hidden=True)[0])
    return jax.block_until_ready(params), hidden_fn


def serve_phase(rep, counter, lm, prompts, chunk, label):
    """Serve ``prompts`` through a fresh engine twice: the first run
    compiles, the second is the steady run.  Returns the streams."""
    import jax

    def run():
        engine = lm.engine(N_SLOTS, max(PROMPT_LENS) + NEW_TOKENS,
                           decode_chunk=chunk)
        rids = [engine.submit(p, NEW_TOKENS) for p in prompts]
        t = time.time()
        out = engine.run()
        jax.block_until_ready(engine.pool)
        return [out[r] for r in rids], time.time() - t, engine

    before = counter.snapshot()
    streams, cold_s, _ = run()
    mid = counter.snapshot()
    again, steady_s, engine = run()
    after = counter.snapshot()
    n_tok = sum(len(s) for s in streams)
    rep.line(f"{label} chunk={chunk}: {len(prompts)} requests, {n_tok} "
             f"tokens, {engine.stats['decode_steps']} decode steps in "
             f"{engine.stats['megasteps']} dispatches; first run "
             f"{cold_s:.2f}s with {mid[0] - before[0]} compiles "
             f"({mid[1] - before[1]:.2f}s compiling, "
             f"{mid[2] - before[2]} persistent-cache hits); steady run "
             f"{steady_s:.3f}s ({n_tok / steady_s:.1f} tok/s) with "
             f"{after[0] - mid[0]} compiles; {_peak_bytes(jax)}")
    rep.check(f"{label} chunk={chunk} steady run has no compile",
              after[0] == mid[0], f"{after[0] - mid[0]} compiles")
    rep.check(f"{label} chunk={chunk} streams repeat", again == streams,
              "second run of the same requests")
    return streams


def serve_chunks(rep, counter, lm, prompts, label):
    """Serve at every ``decode_chunk``; the megastep must not change a
    greedy stream.  Returns the chunk-1 streams."""
    streams = [serve_phase(rep, counter, lm, prompts, c, label)
               for c in CHUNKS]
    rep.check(f"{label} streams equal at decode_chunk {CHUNKS}",
              all(s == streams[0] for s in streams[1:]),
              f"{len(prompts)} streams, bitwise")
    return streams[0]


def generate_streams(lm, prompts):
    """Static ``generate`` over each prompt length's batch, in order."""
    import numpy as np

    out = []
    for i in range(0, len(prompts), REQUESTS_PER_LEN):
        batch = np.stack(prompts[i:i + REQUESTS_PER_LEN])
        toks = np.asarray(lm.generate(batch, NEW_TOKENS))
        out += [list(map(int, row[batch.shape[1]:])) for row in toks]
    return out


def check_generate(rep, lm, prompts, label):
    """Engine streams bitwise equal to static ``generate`` where both decode
    the same batch shapes: an engine of ``REQUESTS_PER_LEN`` slots admits
    each prompt-length group whole, as ``generate`` batches it."""
    engine = lm.engine(REQUESTS_PER_LEN, max(PROMPT_LENS) + NEW_TOKENS)
    rids = [engine.submit(p, NEW_TOKENS) for p in prompts]
    out = engine.run()
    got = [out[r] for r in rids]
    want = generate_streams(lm, prompts)
    same = sum(g == w for g, w in zip(got, want))
    rep.check(f"{label} engine streams equal static generate", got == want,
              f"{same}/{len(want)} streams bitwise, engine of "
              f"{REQUESTS_PER_LEN} slots against generate in batches of "
              f"{REQUESTS_PER_LEN}")


def check_greedy(rep, name, lm, prompts, streams, hidden_fn):
    """Teacher-force every stream through one static forward pass of ``lm``
    and rank each emitted token among the reference logits there."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.model import dense_verify_logits

    n = len(streams[0])
    # Right padding cannot reach back: the model is causal.
    tokens = np.zeros((len(prompts), max(map(len, prompts)) + n - 1),
                      np.int32)
    at = np.zeros((len(prompts), n), np.int32)
    for i, (p, s) in enumerate(zip(prompts, streams)):
        tokens[i, :len(p) + n - 1] = np.concatenate([p, s[:-1]])
        at[i] = np.arange(len(p) - 1, len(p) + n - 1)
    hidden = hidden_fn(lm.params, tokens)[np.arange(len(prompts))[:, None],
                                          at]
    logits = jax.jit(dense_verify_logits, static_argnums=2)(
        lm.params, hidden, lm.cfg)
    head = lm.head
    if head.needs_hidden:   # prefill emits the first token off the dense head
        tail = hidden[:, 1:]
        logits = logits.at[:, 1:].set(jax.jit(head.apply)(
            head.params, tail.reshape(-1, tail.shape[-1])
        ).reshape(*tail.shape[:2], -1))
    want = jnp.asarray(np.asarray(streams, np.int32))
    mine = jnp.take_along_axis(logits, want[..., None], -1)
    rank = np.asarray(jnp.sum(logits > mine, -1))
    finite = bool(jnp.isfinite(logits).all())
    rep.check(name, finite and int(rank.max()) < GREEDY_TOP,
              f"worst rank {int(rank.max())} (bound < {GREEDY_TOP}) among "
              f"{logits.shape[-1]}; {int((rank == 0).sum())}/{rank.size} "
              f"tokens are the reference argmax; logits finite: {finite}")


def sketch_head_checks(rep, lm, hidden, quant):
    """Registry dispatch, the kernel in the compiled decode step, and
    Pallas vs ref logits on the same hiddens."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import registry
    from repro.launch.steps import jitted_serve_fns
    from repro.models.model import init_decode_cache

    head = lm.head
    label = head.describe()
    rep.check(f"{label} kernel backend is pallas",
              registry.resolve("fused_decode")
              is registry.resolve("fused_decode", "pallas"),
              f"registry default {registry.default_backend()!r}")
    decode = jitted_serve_fns(lm.cfg, head.without_params()).decode
    hlo = decode.lower(
        lm.params, init_decode_cache(lm.cfg, N_SLOTS, 1),
        jnp.zeros((N_SLOTS, 1), jnp.int32), jnp.zeros((N_SLOTS,), jnp.int32),
        head_params=head.params,
        active=jnp.ones((N_SLOTS,), bool)).compile().as_text()
    rep.check(f"{label} decode step holds the Pallas kernel",
              "tpu_custom_call" in hlo, "tpu_custom_call in compiled HLO")
    pal = np.asarray(jax.jit(head.apply)(head.params, hidden))
    ref_head = head.with_backend("ref")
    ref = np.asarray(jax.jit(ref_head.apply)(head.params, hidden))
    scale = float(np.abs(ref).max())
    err = float(np.abs(pal - ref).max())
    top1 = float(np.mean(pal.argmax(-1) == ref.argmax(-1)))
    rep.check(f"{label} pallas matches ref",
              bool(np.isfinite(pal).all()) and pal.shape == ref.shape
              and err <= SKETCH_TOL[quant] * scale,
              f"max |pallas - ref| {err:.3e} vs bound "
              f"{SKETCH_TOL[quant]:.0e} x {scale:.3e}, top-1 agreement "
              f"{top1:.4f} over {pal.shape[0]} hiddens")


def cpu_reference(rep, cfg, params, tokens):
    """Prefill logits on the chip vs the same jitted forward on the host
    CPU at highest matmul precision, in f32 and in the served bf16."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.model import forward

    fwd = jax.jit(lambda p, t: forward(p, t, cfg, remat=False)[0])
    cpu = jax.devices("cpu")[0]
    host_params = jax.device_put(params, cpu)
    f32 = lambda p: jax.tree.map(lambda a: a.astype(jnp.float32), p)  # noqa: E731

    t = time.time()
    with jax.default_matmul_precision("highest"):
        chip32 = np.asarray(fwd(f32(params), tokens))
        host32, host = (np.asarray(fwd(p, jax.device_put(tokens, cpu)))
                        for p in (f32(host_params), host_params))
    chip = np.asarray(fwd(params, tokens))
    rep.line(f"CPU reference forwards of {tokens.shape} tokens: "
             f"{time.time() - t:.1f}s (transfer and compile included)")

    def top1(a, b):
        return float(np.mean(a.argmax(-1) == b.argmax(-1)))

    def rms(a, b):
        return float(np.sqrt(np.mean((a - b) ** 2)))

    scale = float(np.abs(host32).max())
    err = float(np.abs(chip32 - host32).max())
    rep.check("f32 prefill logits match the CPU reference",
              bool(np.isfinite(chip32).all())
              and err <= PREFILL_F32_ERR * scale
              and top1(chip32, host32) >= PREFILL_F32_TOP1,
              f"max |tpu - cpu| {err:.3e} = {err / scale:.3e} of max |cpu| "
              f"(bound {PREFILL_F32_ERR}); top-1 agreement "
              f"{top1(chip32, host32):.4f} over {tokens.size} positions "
              f"(bound {PREFILL_F32_TOP1})")
    rep.check("bf16 prefill logits match the CPU reference",
              bool(np.isfinite(chip).all())
              and rms(chip, host) <= PREFILL_BF16_RMS
              and top1(chip, host) >= PREFILL_BF16_TOP1,
              f"rms |tpu - cpu| {rms(chip, host):.4f} (bound "
              f"{PREFILL_BF16_RMS}); max |tpu - cpu| "
              f"{float(np.abs(chip - host).max()):.4f}; top-1 agreement "
              f"{top1(chip, host):.4f} (bound {PREFILL_BF16_TOP1})")


def _setup(rep, args):
    """Config, weights, the distilled sketch head, reference hiddens and
    the requests shared by both modes."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.launch.serve import build_or_load_head

    cfg = get_config(ARCH)
    t = time.time()
    params, hidden_fn = _model(cfg, args.seed)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    rep.line(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, d_ff "
             f"{cfg.d_ff}, vocab {cfg.vocab_size}; params "
             f"{n_bytes / 2**30:.2f} GiB initialised from seed {args.seed} "
             f"in {time.time() - t:.1f}s")
    rng = np.random.default_rng(args.seed + 1)
    ref_tokens = rng.integers(0, cfg.vocab_size, (2, REF_PROMPT_LEN),
                              dtype=np.int32)
    hidden = hidden_fn(params, ref_tokens).reshape(-1, cfg.d_model)
    t = time.time()
    sketch = build_or_load_head(params, cfg, None)
    jax.block_until_ready(sketch.params)
    rep.line(f"sketch head distilled and frozen in {time.time() - t:.1f}s "
             f"(L={sketch.cfg.n_rows}, R={sketch.cfg.n_buckets}, "
             f"K={sketch.cfg.k}, d'={sketch.cfg.proj_dim})")
    return (cfg, params, hidden_fn, ref_tokens, hidden, sketch,
            _requests(cfg, args.seed))


def single_chip(rep, counter, args):
    from repro.api.heads import DenseHead
    from repro.api.lm import LM

    (cfg, params, hidden_fn, ref_tokens, hidden, sketch,
     prompts) = _setup(rep, args)
    lm = LM(params, cfg, DenseHead())
    for served in [lm] + [lm.with_head(sketch.quantized(q)) for q in QUANTS]:
        label = served.head.describe()
        if served.head.kind == "sketch":
            sketch_head_checks(rep, served, hidden, served.head.quant)
        streams = serve_chunks(rep, counter, served, prompts, label)
        check_greedy(rep, f"{label} engine tokens are greedy", served,
                     prompts, streams, hidden_fn)
        check_generate(rep, served, prompts, label)
    # Last: its f32 weights would otherwise count in the serving peaks.
    cpu_reference(rep, cfg, params, ref_tokens)


def four_chips(rep, counter, args):
    """The sharded serving path on a 1x4 mesh, against one device."""
    import jax
    import numpy as np

    from repro.api.heads import DenseHead
    from repro.api.lm import LM

    if len(jax.devices()) != 4:
        _fail(f"--four-chips needs 4 devices, JAX sees {len(jax.devices())}")
    (cfg, params, hidden_fn, _, hidden, sketch,
     prompts) = _setup(rep, args)
    for head in [DenseHead()] + [sketch.quantized(q) for q in QUANTS]:
        single = LM(params, cfg, head)
        mesh_lm = single.with_mesh("1x4")
        label = f"1x4 {head.describe()}"
        used = ", ".join(
            f"{(d.memory_stats() or {}).get('bytes_in_use', 0) / 2**30:.2f}"
            for d in jax.devices())
        rep.line(f"{label}: bytes in use per device {used} GiB")
        if head.kind == "sketch":
            arr = mesh_lm.head.params["array"]
            shard = arr.addressable_shards[0].data.shape
            rep.check(f"{label} count array split over model",
                      shard[0] * 4 == arr.shape[0],
                      f"global {arr.shape}, per-device shard {shard}")
            apply = jax.jit(head.apply, static_argnames="mesh")
            one = np.asarray(apply(head.params, hidden))
            four = np.asarray(apply(mesh_lm.head.params, hidden,
                                    mesh=mesh_lm.mesh))
            scale = float(np.abs(one).max())
            err = float(np.abs(four - one).max())
            rep.check(f"{label} sharded logits match one device",
                      err <= SKETCH_TOL[head.quant] * scale,
                      f"max |1x4 - 1| {err:.3e} vs bound "
                      f"{SKETCH_TOL[head.quant]:.0e} x {scale:.3e}")
        streams = serve_chunks(rep, counter, mesh_lm, prompts, label)
        check_greedy(rep, f"{label} engine tokens are greedy under one "
                     f"device's model", single, prompts, streams, hidden_fn)
        rep.line(f"{label}: {_peak_bytes(jax)} per device")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the requests")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 1x4 sharded serving path and its "
                         "one-device comparison (needs 4 chips)")
    args = ap.parse_args()

    if os.environ.get("REPRO_KERNEL_BACKEND"):
        _fail("REPRO_KERNEL_BACKEND is set; the chip path must run the "
              "Pallas kernels it would serve with")
    # The CPU reference needs the host backend next to the TPU.
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _fail(f"no TPU: JAX's first device is {dev.platform!r} "
              f"({dev.device_kind})")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import use_compile_cache

    rep = Report()
    rep.line(f"device: {dev.platform} / {dev.device_kind} x "
             f"{len(jax.devices())}; jax {jax.__version__}; compile cache "
             f"{use_compile_cache()}")
    counter = CompileCounter()
    if args.four_chips:
        four_chips(rep, counter, args)
    else:
        single_chip(rep, counter, args)
    n, secs, hits = counter.snapshot()
    rep.line(f"total: {n} compiles, {secs:.1f}s compiling, {hits} "
             f"persistent-cache hits; {_peak_bytes(jax)}")
    if rep.failed:
        _fail(f"{len(rep.failed)} checks failed: {rep.failed}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
