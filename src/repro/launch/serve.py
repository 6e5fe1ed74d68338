"""Serving launcher: static batch or continuous-batching engine, any head.

Two serving modes over a (smoke-scale on CPU) model, both routed through the
``repro.api`` facade (``LM`` + ``LogitHead`` + ``Sampler`` — DESIGN.md §8):

* **static** (default) — one synthetic request batch: a single bulk prefill
  ingests every prompt into the decode cache, then the decode loop emits
  tokens step by step until the *slowest* request is done.
* **``--engine``** — the continuous-batching engine (repro.launch.engine,
  DESIGN.md §7): a pool of ``--batch`` cache slots served from a FIFO queue
  with staggered arrivals and skewed per-request generation lengths;
  finished sequences retire individually and their slots are recycled
  mid-decode.  ``--paged --page-size N`` swaps the contiguous slot pool for
  the paged pool + exact-prompt prefix cache (DESIGN.md §13): bitwise the
  same streams, repeated prompts prefill once.  ``--stats-json`` appends
  the engine stats dict as one parseable ``STATS_JSON {…}`` line.

``--sketch-head`` swaps the dense logit matmul for the Representer-Sketch
head (the paper's technique as a first-class serving feature — DESIGN.md §4)
in either mode; ``--backend fused|two_kernel|ref`` picks its decode path
(one fused Pallas call by default).  The head is distilled offline by
examples/serve_sketch_head.py and loaded via ``--head-path``; without a
saved head a quick in-process distillation builds one.  ``--quant
int8|int4`` serves the head from quantized count-array storage (per-row
symmetric scales, dequantized in-register by the decode kernels —
DESIGN.md §12); a ``--head-path`` archive saved quantized loads as-is.

``--mesh <data>x<model>`` serves SPMD over a device mesh in either mode
(params via ``sharding/rules.py``, caches batch-sharded over ``data``,
sketch count arrays over ``model`` with one psum per decode step —
DESIGN.md §9); on CPU force devices first with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

``--decode-chunk K`` moves the decode loop on-device in either mode: K
steps per dispatch as one ``lax.scan`` megastep with sampling and EOS
retirement fused in (launch/decode_loop.py, DESIGN.md §10) — ~1/K the
host syncs, with token streams bitwise K-invariant (static mode always;
engine mode except seeded sampling when a mid-chunk EOS shifts a
re-admission — docs/serving.md).

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --smoke \
      --batch 4 --prompt-len 32 --gen 16 [--sketch-head] [--backend fused] \
      [--quant int8] \
      [--temperature 0.8 --top-k 40 --top-p 0.95] [--decode-chunk 8] \
      [--engine --requests 8 --arrival-every 2] [--mesh 4x2]
"""

from __future__ import annotations

import argparse
import time
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.heads import DenseHead, LogitHead, SketchHead
from repro.api.sampler import Sampler
from repro.configs import get_config
from repro.launch.steps import jitted_serve_fns
from repro.models.config import SketchHeadConfig
from repro.models.model import init_decode_cache, init_model


def generate(params, cfg, prompts: jnp.ndarray, gen_len: int,
             encoder_states=None, *, head: Optional[LogitHead] = None,
             sampler: Optional[Sampler] = None,
             eos_id: Optional[int] = None, pad_id: int = 0,
             return_stats: bool = False, mesh=None, decode_chunk: int = 1,
             spec_decode: int = 0, sketch_head_params=None,
             sketch_cfg: Optional[SketchHeadConfig] = None,
             fused=None, greedy=None, seed=None):
    """Bulk prefill + decode. prompts: (B, P) → tokens (B, P+gen_len).

    ``head`` (a repro.api ``LogitHead``, dense by default) produces the
    per-step logits; ``sampler`` (greedy by default) picks the tokens,
    threading a split key chain from its seed so runs with the same sampler
    reproduce exactly.  With ``eos_id``, a sequence that emits it is
    finished: its later positions hold ``pad_id``, its cache row freezes
    (the engine's parked-slot discipline), and the loop exits early once
    every row is done — finished sequences stop counting toward decode
    work.  ``return_stats=True`` additionally returns ``{"decode_steps"}``.

    ``decode_chunk=K`` (> 1) runs the decode loop on device: sampling and
    EOS retirement fuse into K-step ``lax.scan`` megasteps
    (launch/decode_loop.py, DESIGN.md §10) so only token blocks cross to
    host — same streams, 1/K the host syncs and dispatches.  The default
    ``decode_chunk=1`` keeps the per-token host loop (the bitwise-parity
    reference the megastep is tested against).

    ``spec_decode=K`` (> 0; mutually exclusive with ``decode_chunk > 1``)
    decodes speculatively: ``head`` drafts K tokens per dispatch and one
    batched dense pass verifies them (launch/decode_loop.py, DESIGN.md
    §11).  The emitted stream is bitwise-identical to dense decode with the
    same ``sampler`` — the head only sets how many drafts commit per
    verify; stats gain ``verify_calls`` / ``draft_tokens`` /
    ``accepted_draft_tokens``.

    ``mesh`` serves SPMD over a ``(data, model)`` device mesh: params and
    head arrays are placed per ``sharding/rules.py`` (a no-op when the LM
    facade already placed them), the decode cache batch-shards over
    ``data``, and sketch heads decode on their shard_map path
    (DESIGN.md §9).

    The pre-redesign ``sketch_head_params=/sketch_cfg=/fused=/greedy=/
    seed=`` kwargs keep working behind a DeprecationWarning.
    """
    from repro.launch.steps import resolve_legacy_serving_kwargs
    head, sampler = resolve_legacy_serving_kwargs(
        head, sampler, sketch_head_params, sketch_cfg, fused, greedy, seed,
        "generate()")
    head = head or DenseHead()
    sampler = sampler or Sampler()
    if decode_chunk < 1:
        raise ValueError(f"decode_chunk must be >= 1, got {decode_chunk}")
    if spec_decode < 0:
        raise ValueError(f"spec_decode must be >= 0, got {spec_decode}")
    if spec_decode and decode_chunk > 1:
        raise ValueError("spec_decode and decode_chunk > 1 are mutually "
                         "exclusive: the speculative megastep already "
                         "advances up to K tokens per dispatch")
    b, p = prompts.shape
    max_seq = p + gen_len
    cache = init_decode_cache(cfg, b, max_seq)
    if mesh is not None:
        from repro.launch.mesh import place_serving_state
        from repro.sharding.rules import cache_shardings
        params, head = place_serving_state(params, head, mesh)
        cache = jax.device_put(cache, cache_shardings(cache, mesh))

    # Jitted steps are memoized per (cfg, head spec, mesh) — repeated
    # generate() calls (static-batch chunking, benchmarks) reuse one
    # compile cache.
    prefill, step, _, _ = jitted_serve_fns(cfg, head.without_params(),
                                           mesh=mesh)

    # Bulk prefill: the whole prompt runs in one forward pass that fills the
    # decode cache, replacing the P per-token decode steps of the old loop.
    # Long prompts stay memory-bounded: cached attention switches to the
    # online-softmax chunked path above the same thresholds as training.
    logits, cache = prefill(params, prompts, encoder_states=encoder_states,
                            cache=cache)

    if spec_decode:
        from repro.launch.decode_loop import spec_decode_chunks
        tail, stats = spec_decode_chunks(
            params, cache, logits, cfg=cfg, head=head, sampler=sampler,
            gen_len=gen_len, start_pos=p, spec_k=spec_decode, eos_id=eos_id,
            pad_id=pad_id, mesh=mesh, encoder_states=encoder_states)
        tokens = jnp.concatenate([prompts.astype(jnp.int32), tail], axis=1)
        return (tokens, stats) if return_stats else tokens

    if decode_chunk > 1:
        from repro.launch.decode_loop import decode_chunks
        tail, stats = decode_chunks(
            params, cache, logits, cfg=cfg, head=head, sampler=sampler,
            gen_len=gen_len, start_pos=p, chunk=decode_chunk, eos_id=eos_id,
            pad_id=pad_id, mesh=mesh, encoder_states=encoder_states)
        tokens = jnp.concatenate([prompts.astype(jnp.int32), tail], axis=1)
        return (tokens, stats) if return_stats else tokens

    key = sampler.init_key()
    out = [prompts]
    finished = np.zeros(b, bool)
    stats = {"decode_steps": 0}
    for t in range(gen_len):
        key, nxt = sampler.sample(key, logits)
        if eos_id is not None:
            # EOS bookkeeping needs host values; without eos_id the tokens
            # stay on device so dispatch pipelines across steps.
            nxt_h = np.where(finished, pad_id,
                             np.asarray(nxt, np.int32)).astype(np.int32)
            finished |= nxt_h == eos_id
            nxt = jnp.asarray(nxt_h)
        nxt = nxt[:, None].astype(jnp.int32)
        out.append(nxt)
        if t == gen_len - 1:
            break  # the last token needs no forward — its logits are unused
        if eos_id is not None and finished.all():
            # Early stop: every sequence is done; the rest is padding.
            out.append(jnp.full((b, gen_len - 1 - t), pad_id, jnp.int32))
            break
        active = jnp.asarray(~finished) if eos_id is not None else None
        logits, cache = step(params, cache, nxt,
                             jnp.asarray(p + t, jnp.int32),
                             encoder_states=encoder_states,
                             head_params=head.params, active=active)
        stats["decode_steps"] += 1
    tokens = jnp.concatenate(out, axis=1)
    return (tokens, stats) if return_stats else tokens


def build_or_load_head(params, cfg, head_path: str | None,
                       backend: str | None = None,
                       distill_steps: int = 300,
                       quant: str | None = None) -> SketchHead:
    """Load a frozen sketch head, or distill one from the dense head now.

    The offline path (examples/serve_sketch_head.py) distills at a real
    budget and saves with ``SketchHead.save``; this fallback runs a short
    distillation so ``--sketch-head`` is self-contained at smoke scale.
    Returns a ready-to-serve :class:`repro.api.SketchHead`.  ``backend=None``
    keeps a loaded head on the decode backend it was saved with (the
    kind/backend round-trip); an explicit value overrides it.  ``quant``
    quantizes the count array post-load/post-freeze (``int8``/``int4``,
    DESIGN.md §12); it is a no-op when a loaded archive already carries the
    requested mode, and an error if it carries a different one.
    """
    from repro.core.distill import DistillConfig
    from repro.core.sketch_lm_head import distill_head, freeze_head

    if head_path:
        if not Path(head_path).exists():
            raise FileNotFoundError(
                f"--head-path {head_path} does not exist; run "
                f"examples/serve_sketch_head.py to distill and save a head, "
                f"or drop --head-path to distill one in-process")
        head = SketchHead.load(head_path)
        if backend is not None:
            head = head.with_backend(backend)
        l, r, v = head.params["array"].shape
        d = head.params["proj"].shape[0]
        if v != cfg.vocab_size or d != cfg.d_model:
            raise ValueError(
                f"sketch head {head_path} was frozen for (d_model={d}, "
                f"vocab={v}) but --arch {cfg.name} has "
                f"(d_model={cfg.d_model}, vocab={cfg.vocab_size})")
        if quant is not None and head.quant != quant:
            head = head.quantized(quant)   # raises on a conflicting mode
        print(f"loaded sketch head from {head_path} "
              f"(L={head.cfg.n_rows}, R={head.cfg.n_buckets}, "
              f"backend={head.backend}, quant={head.quant})")
        return head

    head_cfg = cfg.sketch_head or SketchHeadConfig(
        n_rows=128, n_buckets=16, k=1, proj_dim=32, bandwidth=2.0)
    table = params["embed"] if cfg.tie_embeddings else params["head"]
    hiddens = jax.random.normal(jax.random.PRNGKey(11),
                                (1024, cfg.d_model))
    print(f"distilling sketch head (L={head_cfg.n_rows}, "
          f"R={head_cfg.n_buckets}, {distill_steps} steps) …")
    kparams, metrics = distill_head(
        jax.random.PRNGKey(12), table, hiddens, head_cfg, n_points=256,
        distill_cfg=DistillConfig(n_steps=distill_steps, lr=5e-3))
    print(f"  distill MSE: {metrics['final_mse']:.5f}")
    return SketchHead(cfg=head_cfg, backend=backend or "fused", quant=quant,
                      params=freeze_head(jax.random.PRNGKey(13), kparams,
                                         head_cfg, quant=quant))


def build_tenant_heads(params, cfg, n_tenants: int,
                       backend: str | None = None, quant: str | None = None,
                       distill_steps: int = 300):
    """One shared quick distillation, ``n_tenants`` per-tenant freezes.

    Every tenant shares the distilled anchor set (points/alphas/transform)
    but freezes its own hash bank from a distinct key, so tenants emit
    genuinely different token streams at identical quality — the shape of
    a fleet serving one base model with per-customer heads (DESIGN.md §14).

    Returns ``(shared SketchHead spec, {tenant name: frozen params})``.
    """
    from repro.core.distill import DistillConfig
    from repro.core.sketch_lm_head import distill_head, freeze_head

    head_cfg = cfg.sketch_head or SketchHeadConfig(
        n_rows=128, n_buckets=16, k=1, proj_dim=32, bandwidth=2.0)
    table = params["embed"] if cfg.tie_embeddings else params["head"]
    hiddens = jax.random.normal(jax.random.PRNGKey(11), (1024, cfg.d_model))
    print(f"distilling shared tenant head (L={head_cfg.n_rows}, "
          f"R={head_cfg.n_buckets}, {distill_steps} steps) …")
    kparams, metrics = distill_head(
        jax.random.PRNGKey(12), table, hiddens, head_cfg, n_points=256,
        distill_cfg=DistillConfig(n_steps=distill_steps, lr=5e-3))
    print(f"  distill MSE: {metrics['final_mse']:.5f}")
    spec = SketchHead(cfg=head_cfg, backend=backend or "fused", quant=quant)
    heads = {f"tenant-{t}": freeze_head(jax.random.PRNGKey(100 + t),
                                        kparams, head_cfg, quant=quant)
             for t in range(n_tenants)}
    return spec, heads


def _device_name() -> str:
    dev = jax.devices()[0]
    return f"{len(jax.devices())}x {dev.platform}/{dev.device_kind}"


def run_engine(lm, args, sampler: Sampler, head_cache=None) -> None:
    """Serve a synthetic request stream through the continuous-batching
    engine: staggered arrivals, skewed generation lengths, recycled slots.
    With ``--paged``, repeated prompts in the stream hit the prefix cache
    and skip their prefill entirely.  With ``--tenants N`` (``head_cache``
    set), requests round-robin over N per-tenant heads paged through the
    LRU HeadCache."""
    n_requests = args.requests or 2 * args.batch
    max_seq = args.prompt_len + args.gen
    engine = lm.engine(n_slots=args.batch, max_seq=max_seq, sampler=sampler,
                       decode_chunk=args.decode_chunk,
                       spec_decode=args.spec_decode, paged=args.paged,
                       page_size=args.page_size, head_cache=head_cache)
    rng = np.random.default_rng(args.seed)
    # A quarter of the prompt stream repeats a shared prompt so --paged has
    # prefix-cache traffic to show; the rest are unique.
    shared = rng.integers(0, lm.cfg.vocab_size, args.prompt_len,
                          dtype=np.int32)
    for i in range(n_requests):
        if i % 4 == 3:
            prompt = shared
        else:
            prompt = rng.integers(0, lm.cfg.vocab_size, args.prompt_len,
                                  dtype=np.int32)
        # Skewed length mix: even requests are short, odd run the full --gen.
        gen = args.gen if i % 2 else max(1, args.gen // 4)
        tenant = (f"tenant-{i % args.tenants}" if head_cache is not None
                  else None)
        engine.submit(prompt, gen, arrival=i * args.arrival_every,
                      tenant=tenant)

    t0 = time.time()
    finished = engine.run()
    dur = time.time() - t0
    n_generated = sum(len(v) for v in finished.values())
    print(f"arch={lm.cfg.name} head={lm.head.describe()} engine served "
          f"{len(finished)} requests over {args.batch} slots: "
          f"{n_generated} tokens in {dur:.1f}s wall clock on "
          f"{_device_name()}, compiles included (not a speed), "
          f"{engine.stats['decode_steps']} decode steps in "
          f"{engine.stats['megasteps']} dispatches (chunk "
          f"{engine.decode_chunk}), "
          f"slot utilization {engine.slot_utilization:.2f}")
    if engine.spec_decode:
        drafted = engine.stats["draft_tokens"]
        accepted = engine.stats["accepted_draft_tokens"]
        print(f"speculative: K={engine.spec_decode}, "
              f"{engine.stats['verify_calls']} verify calls, "
              f"acceptance {accepted}/{drafted} "
              f"({accepted / max(1, drafted):.2f})")
    if engine.paged:
        s = engine.stats
        print(f"paged: page_size={engine.page_size}, prefix hits "
              f"{s['prefix_hits']}/{s['prefix_queries']} "
              f"(rate {s['prefix_hits'] / max(1, s['prefix_queries']):.2f}), "
              f"{s['prefill_batches']} prefill batches, "
              f"{s['cow_copies']} COW copies, "
              f"pages in use peak {s['pages_in_use_peak']}")
    if head_cache is not None:
        hs = head_cache.stats
        print(f"tenants: {args.tenants} over HeadCache capacity "
              f"{head_cache.capacity}, hits {hs['hits']}/"
              f"{hs['hits'] + hs['misses']}, {hs['loads']} loads, "
              f"{hs['evictions']} evictions")
    first = finished[min(finished)]
    print("sample token ids:", np.asarray(first[:24]))
    if args.stats_json:
        # One parseable line: the engine stats dict plus run metadata, for
        # scripts/CI that scrape serving numbers without parsing prose.
        import json
        record = {"arch": lm.cfg.name, "head": lm.head.describe(),
                  "n_slots": args.batch, "requests": len(finished),
                  "tokens": n_generated, "seconds": round(dur, 3),
                  "paged": engine.paged,
                  "page_size": engine.page_size if engine.paged else None}
        record.update({k: int(v) for k, v in engine.stats.items()})
        if head_cache is not None:
            record["tenants"] = {
                "n_tenants": args.tenants,
                "capacity": head_cache.capacity,
                **{k: int(v) for k, v in head_cache.stats.items()}}
        print("STATS_JSON " + json.dumps(record, sort_keys=True))


def main() -> None:
    from repro.api.lm import LM
    from repro.launch.compile_cache import use_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6-1.6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="static batch size / engine slot count")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16,
                    help="generation length (engine: per-request max; the "
                         "synthetic mix skews between gen//4 and gen)")
    ap.add_argument("--sketch-head", action="store_true",
                    help="decode with the Representer-Sketch head instead "
                         "of the dense logit matmul")
    ap.add_argument("--head-path", default=None,
                    help="frozen head .npz from examples/serve_sketch_head.py")
    ap.add_argument("--backend", default=None,
                    choices=["fused", "two_kernel", "ref"],
                    help="sketch-head decode backend (DESIGN.md §8); "
                         "default: the backend a --head-path head was saved "
                         "with, else fused")
    ap.add_argument("--quant", default=None, choices=["int8", "int4"],
                    help="serve the sketch head from quantized count-array "
                         "storage (per-row symmetric scales, in-register "
                         "dequant — DESIGN.md §12)")
    ap.add_argument("--no-fused", action="store_true",
                    help="deprecated: alias for --backend two_kernel")
    ap.add_argument("--engine", action="store_true",
                    help="serve a request stream through the "
                         "continuous-batching engine instead of one static "
                         "batch")
    ap.add_argument("--requests", type=int, default=0,
                    help="engine mode: number of requests (default 2×batch)")
    ap.add_argument("--arrival-every", type=int, default=1,
                    help="engine mode: ticks between request arrivals")
    ap.add_argument("--decode-chunk", type=int, default=1,
                    help="decode K tokens per on-device megastep "
                         "(launch/decode_loop.py, DESIGN.md §10); 1 = the "
                         "per-token host loop (bitwise-parity default)")
    ap.add_argument("--spec-decode", type=int, default=0,
                    help="speculative self-decode: the serving head drafts "
                         "K tokens per dispatch, one batched dense pass "
                         "verifies (DESIGN.md §11; output is bitwise the "
                         "dense stream; mutually exclusive with "
                         "--decode-chunk > 1)")
    ap.add_argument("--paged", action="store_true",
                    help="engine mode: paged decode-cache pool + exact-"
                         "prompt prefix cache (DESIGN.md §13) — bitwise the "
                         "contiguous stream, repeated prompts prefill once; "
                         "mutually exclusive with --decode-chunk > 1 and "
                         "--spec-decode")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per cache page with --paged (smaller pages "
                         "waste less tail memory but deepen the page table)")
    ap.add_argument("--tenants", type=int, default=0,
                    help="engine mode with --sketch-head: serve N per-tenant "
                         "heads (one shared distillation, per-tenant hash "
                         "banks) paged through an LRU HeadCache; requests "
                         "round-robin over tenants (DESIGN.md §14; mutually "
                         "exclusive with --spec-decode and --head-path)")
    ap.add_argument("--stats-json", action="store_true",
                    help="engine mode: print the engine stats dict as one "
                         "parseable 'STATS_JSON {…}' line after the run")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0,
                    help="sampling / request-stream seed")
    ap.add_argument("--mesh", default=None,
                    help="serve SPMD over a '<data>x<model>' device mesh "
                         "(e.g. '4x2'); on CPU, force devices first with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    args = ap.parse_args()
    if args.no_fused and args.backend is not None:
        ap.error("--no-fused is a deprecated alias for --backend two_kernel; "
                 "pass only --backend")
    if (args.paged or args.stats_json) and not args.engine:
        ap.error("--paged/--stats-json apply to engine mode; add --engine")
    if args.tenants:
        if not (args.engine and args.sketch_head):
            ap.error("--tenants needs --engine and --sketch-head")
        if args.head_path:
            ap.error("--tenants distills one shared head in-process; "
                     "--head-path is not supported")
        if args.spec_decode:
            ap.error("--tenants and --spec-decode are mutually exclusive "
                     "(the draft/verify megastep cannot re-gather per-slot "
                     "tenant bindings mid-draft)")
    backend = "two_kernel" if args.no_fused else args.backend

    use_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    params = init_model(jax.random.PRNGKey(0), cfg)
    if args.quant and not args.sketch_head:
        ap.error("--quant only applies to the sketch head; add --sketch-head")
    head = DenseHead()
    head_cache = None
    if args.tenants:
        from repro.api.heads import HeadCache
        head, tenant_heads = build_tenant_heads(params, cfg, args.tenants,
                                                backend, quant=args.quant)
        # Capacity below the tenant count (when traffic allows) so the smoke
        # run exercises paging in/out, not just residency.
        head_cache = HeadCache(tenant_heads.__getitem__,
                               capacity=max(1, min(args.tenants, args.batch)))
    elif args.sketch_head:
        head = build_or_load_head(params, cfg, args.head_path, backend,
                                  quant=args.quant)
    lm = LM(params, cfg, head)
    if args.mesh:
        lm = lm.with_mesh(args.mesh)
        print(f"serving over mesh {dict(zip(lm.mesh.axis_names, lm.mesh.devices.shape))}")
    sampler = Sampler(temperature=args.temperature, top_k=args.top_k,
                      top_p=args.top_p, seed=args.seed)

    if args.engine:
        run_engine(lm, args, sampler, head_cache=head_cache)
        return

    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    enc = None
    if cfg.n_encoder_tokens:
        enc = jax.random.normal(
            jax.random.PRNGKey(2),
            (args.batch, cfg.n_encoder_tokens, cfg.d_model), jnp.bfloat16)

    t0 = time.time()
    out = lm.generate(prompts, args.gen, sampler=sampler,
                      encoder_states=enc, decode_chunk=args.decode_chunk,
                      spec_decode=args.spec_decode,
                      return_stats=bool(args.spec_decode))
    stats = None
    if args.spec_decode:
        out, stats = out
    dur = time.time() - t0
    total_tokens = args.batch * (args.prompt_len + args.gen)
    print(f"arch={cfg.name} head={lm.head.describe()} served {args.batch} "
          f"seqs, {total_tokens} tokens in {dur:.1f}s wall clock on "
          f"{_device_name()}, compiles included (not a speed)")
    if stats is not None:
        print(f"speculative: K={args.spec_decode}, "
              f"{stats['verify_calls']} verify calls, acceptance "
              f"{stats['accepted_draft_tokens']}/{stats['draft_tokens']} "
              f"({stats['accepted_draft_tokens'] / max(1, stats['draft_tokens']):.2f})")
    print("sample token ids:", np.asarray(out[0, :24]))


if __name__ == "__main__":
    main()
