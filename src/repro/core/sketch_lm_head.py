"""Representer-Sketch LM head: distill a dense logit head into per-class
RACE arrays (DESIGN.md §4 — the paper's technique as a serving feature).

The dense head computes ``logits = h · Wᵀ`` (2·d·V FLOPs/token).  We treat
each vocab class v as one output channel of a weighted kernel function

    f_K(h)[v] = Σ_j α_{j,v} · K(Aᵀh, x_j)

with *shared* anchors x_j and a shared asymmetric projection A (§4.3 of the
paper), distilled from the dense head's logits by MSE.  Freezing gives one
(L, R, V) sketch whose decode cost is L·V adds + a d×d' projection —
replacing 2·d·V multiplies.  The paper's noted limitation (memory linear in
V) is explicit here: memory = L·R·V vs d·V dense, a win iff L·R < d — and
the *storage* claim (up to 114×) additionally needs the counts narrower
than f32: ``quant="int8"|"int4"`` stores per-row symmetric-quantized counts
plus (L, R) f32 scales, dequantized in-register by the decode kernels
(DESIGN.md §12).

Decode-path kernels: repro.kernels.fused_decode (transform → hash → gather in
one pallas_call — the serving default), or the two-kernel composition of
repro.kernels.lsh_hash (projection+hash) and repro.kernels.sketch_head
(shared-index gather as MXU one-hot matvec), kept as the unfused baseline.
"""

from __future__ import annotations

import dataclasses
import types
import typing
import warnings
from pathlib import Path
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distill import DistillConfig, distill
from repro.core.kernel_model import KernelModel, KernelModelConfig
from repro.core.lsh import L2LSH, LSHConfig
from repro.kernels.common import (HASH_PRECISION, pack_int4_rows,
                                  unpack_int4_rows)
from repro.kernels.fused_decode.ops import fused_decode_logits
from repro.kernels.lsh_hash.ops import lsh_hash
from repro.kernels.race_update.ops import race_update
from repro.kernels.sketch_head.ops import sketch_head_logits
from repro.models.config import SketchHeadConfig
from repro.optim.compress import quantize_symmetric

#: Count-array storage modes.  ``quant`` is *static* everywhere (it selects
#: kernel code paths); the scales travel in the head dict as a traced leaf.
QUANT_MODES = (None, "int8", "int4")

#: Current .npz archive format.  v1 = pre-version f32-only archives (still
#: loadable); v2 adds ``meta_format_version`` / ``meta_quant`` / ``scale``.
HEAD_FORMAT_VERSION = 2


def distill_head(
    key: jax.Array,
    head_table: jnp.ndarray,          # (V, d) dense head weights
    hidden_samples: jnp.ndarray,      # (N, d) representative final hiddens
    cfg: SketchHeadConfig,
    *,
    n_points: int = 512,
    distill_cfg: DistillConfig = DistillConfig(n_steps=1500, lr=5e-3),
) -> Tuple[dict, Dict[str, float]]:
    """Learn (anchors, alphas, proj) matching the dense head's logits."""
    v, d = head_table.shape
    model = KernelModel(KernelModelConfig(
        in_dim=d, proj_dim=cfg.proj_dim, n_points=n_points, n_outputs=v,
        bandwidth=cfg.bandwidth, k=cfg.k))
    teacher = lambda h: (h.astype(jnp.float32)
                         @ head_table.astype(jnp.float32).T)
    params, metrics = distill(key, teacher, hidden_samples, model, distill_cfg)
    return params, metrics


def _transform(hidden: jnp.ndarray, proj: jnp.ndarray) -> jnp.ndarray:
    """The asymmetric transform q = h·A, at the hash precision."""
    return jnp.matmul(hidden.astype(jnp.float32), proj,
                      precision=HASH_PRECISION)


def _check_quant(quant: Optional[str]) -> None:
    if quant not in QUANT_MODES:
        raise ValueError(f"unknown quant mode {quant!r}; "
                         f"expected one of {QUANT_MODES}")


def quantize_counts(array: jnp.ndarray, quant: str,
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row symmetric quantization of an (L, R, V) count array.

    Returns ``(store, scale)``: the int8 storage carrier — (L, R, V) int8
    for ``"int8"``, (⌈L/2⌉, R, V) packed bytes for ``"int4"`` — and the
    (L, R) f32 per-row scales.  One scale per gathered V-row keeps the
    dequant a single multiply inside the decode kernels.
    """
    _check_quant(quant)
    bits = {"int8": 8, "int4": 4}[quant]
    q, scale = quantize_symmetric(array, bits=bits, axis=-1)
    if quant == "int4":
        q = pack_int4_rows(q)
    return q, scale


def quantize_head(head: dict, quant: Optional[str]) -> dict:
    """Quantize a frozen f32 head's count array in place of ``"array"``.

    Adds the ``"scale"`` leaf; hash/transform params stay f32 (they are
    negligible next to the counts — see :func:`head_costs`).  ``None`` is a
    no-op copy, so callers can thread a config switch straight through.
    """
    _check_quant(quant)
    if "scale" in head:
        raise ValueError("head is already quantized (has a 'scale' leaf)")
    if quant is None:
        return dict(head)
    store, scale = quantize_counts(head["array"], quant)
    out = dict(head)
    out["array"] = store
    out["scale"] = scale
    return out


def dequantize_head(head: dict, quant: Optional[str],
                    n_rows: Optional[int] = None) -> dict:
    """Materialize the f32 head back from quantized storage (debug/eval).

    ``n_rows`` (true L) is needed for int4 only when it cannot be read off
    the hash bank ``head["w"]``.
    """
    _check_quant(quant)
    if quant is None:
        return dict(head)
    store = head["array"]
    if quant == "int4":
        l = n_rows if n_rows is not None else head["w"].shape[0]
        store = unpack_int4_rows(store, l)
    out = {k: v for k, v in head.items() if k != "scale"}
    out["array"] = store.astype(jnp.float32) * head["scale"][:, :, None]
    return out


def freeze_head(key: jax.Array, kernel_params: dict,
                cfg: SketchHeadConfig, *,
                quant: Optional[str] = None) -> dict:
    """Build the deployable sketch-head params from distilled kernel params.

    ``quant`` quantizes the count array on freeze (int8/int4 per-row
    symmetric; adds a ``"scale"`` leaf) — the deployable artifact never
    materializes f32 counts again.
    """
    points = kernel_params["points"]      # (M, d')
    alphas = kernel_params["alphas"]      # (M, V)
    lsh = L2LSH(LSHConfig(n_rows=cfg.n_rows, n_buckets=cfg.n_buckets,
                          k=cfg.k, dim=cfg.proj_dim, bandwidth=cfg.bandwidth))
    hash_params = lsh.params(key)
    idx = lsh.hash(hash_params, points)   # (M, L)
    onehot = jax.nn.one_hot(idx, cfg.n_buckets, dtype=jnp.float32)  # (M,L,R)
    # (L, R, V) — class-shared layout for the decode kernel.
    array = jnp.einsum("mlr,mv->lrv", onehot, alphas.astype(jnp.float32))
    head = {
        "proj": kernel_params["proj"],            # (d, d')
        "w": hash_params["w"],                    # (L, K, d')
        "b": hash_params["b"],                    # (L, K)
        "array": array,                           # (L, R, V)
    }
    return quantize_head(head, quant)


def stack_heads(heads) -> dict:
    """Stack per-tenant frozen head dicts into one tenant-indexed bank.

    Every leaf gains a leading tenant axis T — the layout the multi-tenant
    decode paths gather from by slot tenant-id (DESIGN.md §14).  All heads
    must share shapes, dtypes, and quantization (the bank is one jit
    operand; mixed storage would need per-tenant executables).
    """
    heads = list(heads)
    if not heads:
        raise ValueError("stack_heads needs at least one head")
    keys = set(heads[0])
    for h in heads[1:]:
        if set(h) != keys:
            raise ValueError(
                f"cannot stack heads with different leaves: {sorted(keys)} "
                f"vs {sorted(h)} — mixed quantization across tenants is not "
                f"supported")
    return {k: jnp.stack([jnp.asarray(h[k]) for h in heads]) for k in keys}


def refresh_head(head: dict, cfg: SketchHeadConfig, hidden: jnp.ndarray,
                 *, alphas: Optional[jnp.ndarray] = None,
                 targets: Optional[jnp.ndarray] = None, lr: float = 1.0,
                 backend: Optional[str] = None) -> dict:
    """Fold live-traffic (hidden, logit) pairs into the count arrays online.

    The streaming-update path the RACE sketch was designed for
    (``kernels/race_update``, DESIGN.md §14): hash the (M, d_model) hiddens
    through the head's own transform + bank, then accumulate the per-point
    weights into the (L, R, V) counts.  Exactly one of

    * ``alphas`` — (M, V) direct fold: the new points join the anchor set
      with these representer weights, mathematically identical to
      :func:`freeze_head` over the augmented set (same einsum, so a
      refresh-then-publish matches offline re-distillation on the same
      stream up to f32 summation order);
    * ``targets`` — (M, V) residual fold for live traffic: the weights are
      ``lr · (targets − f(hidden))``, a functional-gradient step toward the
      observed teacher logits.

    ``head`` must be the f32 working copy (refresh accumulates in f32;
    dequantize a quantized head first and re-quantize on publish — the
    engine's double-buffered ``refresh``/``publish`` does both).
    """
    if "scale" in head:
        raise ValueError(
            "refresh_head accumulates in f32; dequantize the head first "
            "(dequantize_head) and re-quantize on publish — see "
            "ServeEngine.refresh")
    if (alphas is None) == (targets is None):
        raise ValueError("pass exactly one of alphas= (direct fold) / "
                         "targets= (residual fold)")
    q = _transform(hidden, head["proj"])
    idx = lsh_hash(q, head["w"], head["b"], bandwidth=cfg.bandwidth,
                   n_buckets=cfg.n_buckets, backend=backend)       # (M, L)
    if targets is not None:
        pred = apply_head(head, hidden, cfg, backend="ref")
        alphas = lr * (targets.astype(jnp.float32) - pred)
    # race_update accumulates a (C, L, R) sketch; the head stores (L, R, V).
    # One class per vocab entry: move V to the class axis and back.
    sk = jnp.moveaxis(head["array"], -1, 0)                        # (V, L, R)
    sk = race_update(sk, idx, alphas.astype(jnp.float32), backend=backend)
    out = dict(head)
    out["array"] = jnp.moveaxis(sk, 0, -1)
    return out


#: Decode backends of the sketched head (see repro.api.heads.SketchHead).
HEAD_BACKENDS = ("fused", "two_kernel", "ref")


def apply_head(head: dict, hidden: jnp.ndarray, cfg: SketchHeadConfig,
               *, backend: Optional[str] = None,
               kernel_backend: Optional[str] = None,
               quant: Optional[str] = None,
               mesh=None, tenant_ids: Optional[jnp.ndarray] = None,
               use_pallas=None, fused=None) -> jnp.ndarray:
    """Sketched logits for (B, d) final hiddens → (B, V).

    ``backend`` selects the decode path:

    * ``"fused"``      — the whole head in one pallas_call (the serving hot
      path — no HBM round trip on the (B, L) index tensor; default),
    * ``"two_kernel"`` — the lsh_hash → sketch_head composition kept as the
      unfused baseline,
    * ``"ref"``        — the pure-jnp oracle composition (CPU/CI parity).

    ``kernel_backend`` optionally forces the kernel registry's pallas/ref
    choice for this call (otherwise ``REPRO_KERNEL_BACKEND`` / the registry
    default applies); ``backend="ref"`` already pins it to ``"ref"``, so
    combining it with ``kernel_backend="pallas"`` is a contradiction and
    raises.  ``quant`` declares the head's count-array storage (static;
    must match the presence of the head's ``"scale"`` leaf).  ``mesh`` (a
    ``jax.sharding.Mesh`` with a ``model`` axis) runs the head on the
    row-sharded shard_map path: count arrays partitioned over ``model`` on
    the repetition axis, scales with their rows, one psum of the (B, V)
    partials per step (DESIGN.md §9) — any ``backend`` composes with it.
    ``tenant_ids`` ((B,) int32) selects the multi-tenant path (DESIGN.md
    §14): ``head`` is a tenant-stacked bank (:func:`stack_heads`, leading
    axis T on every leaf), each resident tenant's logits are computed over
    the full batch by the identical single-tenant path, and row ``b`` takes
    tenant ``tenant_ids[b]``'s row arithmetic-free — bitwise what a
    single-tenant run bound to that head emits.  ``use_pallas=`` /
    ``fused=`` are deprecated aliases.
    """
    if fused is not None or use_pallas is not None:
        warnings.warn(
            "apply_head(fused=..., use_pallas=...) is deprecated; pass "
            "backend='fused'|'two_kernel'|'ref' (and kernel_backend= for "
            "the pallas/ref choice) instead", DeprecationWarning,
            stacklevel=2)
        if backend is None:
            backend = "fused" if fused else "two_kernel"
        if kernel_backend is None and use_pallas is not None:
            kernel_backend = "pallas" if use_pallas else "ref"
    if backend is None:
        backend = "fused"
    if backend == "ref":
        if kernel_backend not in (None, "ref"):
            raise ValueError(
                "apply_head(backend='ref') is the pure-jnp oracle and always "
                f"runs kernel_backend='ref'; got kernel_backend="
                f"{kernel_backend!r} — drop it or use backend='fused'/"
                "'two_kernel' to pick the kernel implementation")
        backend, kernel_backend = "two_kernel", "ref"
    _check_quant(quant)
    if (quant is not None) != ("scale" in head):
        raise ValueError(
            f"quant={quant!r} inconsistent with head params: a quantized "
            "head carries a 'scale' leaf and needs the matching quant= "
            "(got scale " + ("present" if "scale" in head else "absent") + ")")
    scale = head.get("scale")
    if backend == "fused":
        return fused_decode_logits(
            hidden.astype(jnp.float32), head["proj"], head["w"], head["b"],
            head["array"], bandwidth=cfg.bandwidth, n_buckets=cfg.n_buckets,
            scale=scale, quant=quant, backend=kernel_backend, mesh=mesh,
            tenant_ids=tenant_ids)
    if backend != "two_kernel":
        raise ValueError(f"unknown sketch-head backend {backend!r}; "
                         f"expected one of {HEAD_BACKENDS}")
    if tenant_ids is not None:
        # Per-tenant transforms and hash banks: each tenant hashes the full
        # batch through its own (proj, w, b) — lsh_hash itself is unchanged
        # — and the (T, B, L) index stack feeds the tenant-aware gather.
        h32 = hidden.astype(jnp.float32)
        idx = jnp.stack([
            lsh_hash(_transform(h32, head["proj"][t]), head["w"][t],
                     head["b"][t],
                     bandwidth=cfg.bandwidth, n_buckets=cfg.n_buckets,
                     backend=kernel_backend)
            for t in range(head["w"].shape[0])])
        return sketch_head_logits(head["array"], idx, scale=scale,
                                  quant=quant, backend=kernel_backend,
                                  mesh=mesh, tenant_ids=tenant_ids)
    q = _transform(hidden, head["proj"])
    idx = lsh_hash(q, head["w"], head["b"], bandwidth=cfg.bandwidth,
                   n_buckets=cfg.n_buckets, backend=kernel_backend)
    return sketch_head_logits(head["array"], idx, scale=scale, quant=quant,
                              backend=kernel_backend, mesh=mesh)


def save_head(path, head: dict, cfg: SketchHeadConfig, *,
              kind: str = "sketch", backend: str = "fused",
              quant: Optional[str] = None) -> None:
    """Persist a frozen head (+ its static config) as a compressed .npz.

    ``kind`` / ``backend`` / ``quant`` are the head-registry identity
    (repro.api.heads); they round-trip through :func:`load_head_meta` so a
    loaded head serves on the same decode path it was saved with.  Archives
    carry ``meta_format_version`` (= :data:`HEAD_FORMAT_VERSION`); config
    fields whose value is ``None`` are skipped and restored from the
    dataclass defaults on load.
    """
    _check_quant(quant)
    if (quant is not None) != ("scale" in head):
        raise ValueError(f"quant={quant!r} inconsistent with head params "
                         "(see apply_head)")
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    np.savez_compressed(
        path, **{k: np.asarray(v) for k, v in head.items()},
        meta_format_version=np.asarray(HEAD_FORMAT_VERSION),
        meta_kind=np.asarray(kind), meta_backend=np.asarray(backend),
        meta_quant=np.asarray("none" if quant is None else quant),
        **{f"cfg_{f.name}": getattr(cfg, f.name)
           for f in dataclasses.fields(cfg)
           if getattr(cfg, f.name) is not None})


def _coerce_config_value(value, typ):
    """Coerce one archived config value to its dataclass field type.

    Handles the types a config dataclass actually uses — int, float, bool,
    str, and Optional[...] of those — from the 0-d numpy arrays an .npz
    round-trip produces.  bool is checked before int (a bool *is* an int);
    unknown types fall back to the raw ``.item()`` value.
    """
    origin = typing.get_origin(typ)
    if origin is typing.Union or origin is getattr(types, "UnionType", None):
        args = [a for a in typing.get_args(typ) if a is not type(None)]
        if len(args) == 1:                  # Optional[T] → T (None values
            typ = args[0]                   # are never written, see save)
    v = value.item() if isinstance(value, np.ndarray) and value.ndim == 0 \
        else value
    if typ is bool:
        return bool(v)
    if typ is int:
        return int(v)
    if typ is float:
        return float(v)
    if typ is str:
        return str(v)
    return v


def coerce_config(cls, raw: Dict[str, object]):
    """Build a config dataclass from raw archive values, field-typed.

    ``raw`` maps field names to archived values; missing fields fall back
    to the dataclass defaults (forward compat for fields added after the
    archive was written).  Field types are resolved through
    ``typing.get_type_hints`` — the config module uses
    ``from __future__ import annotations``, so ``field.type`` is a string.
    """
    hints = typing.get_type_hints(cls)
    kwargs = {}
    for f in dataclasses.fields(cls):
        if f.name in raw:
            kwargs[f.name] = _coerce_config_value(raw[f.name], hints[f.name])
    return cls(**kwargs)


def _meta_from_archive(data) -> Dict[str, object]:
    quant = str(data["meta_quant"]) if "meta_quant" in data else "none"
    return {
        "format_version": (int(data["meta_format_version"])
                           if "meta_format_version" in data else 1),
        "kind": str(data["meta_kind"]) if "meta_kind" in data else "sketch",
        "backend": (str(data["meta_backend"])
                    if "meta_backend" in data else "fused"),
        "quant": None if quant == "none" else quant,
    }


def load_head_full(path) -> Tuple[dict, SketchHeadConfig, Dict[str, object]]:
    """One archive read → (frozen params, config, registry metadata).

    Accepts every archive version: v1 (pre-version, pre-quant, uncompressed)
    archives load unchanged as the historical default — the fused f32
    sketch head.  Metadata keys: ``format_version``, ``kind``, ``backend``,
    ``quant`` (``None`` for f32 heads).
    """
    with np.load(Path(path)) as data:
        keys = ["proj", "w", "b", "array"]
        if "scale" in data:
            keys.append("scale")
        head = {k: jnp.asarray(data[k]) for k in keys}
        cfg = coerce_config(SketchHeadConfig, {
            f.name: data[f"cfg_{f.name}"]
            for f in dataclasses.fields(SketchHeadConfig)
            if f"cfg_{f.name}" in data})
        meta = _meta_from_archive(data)
    if (meta["quant"] is not None) != ("scale" in head):
        raise ValueError(f"corrupt head archive {path}: meta_quant="
                         f"{meta['quant']!r} but scale leaf "
                         + ("present" if "scale" in head else "missing"))
    return head, cfg, meta


def load_head(path) -> Tuple[dict, SketchHeadConfig]:
    """Load a frozen head saved by :func:`save_head`."""
    head, cfg, _ = load_head_full(path)
    return head, cfg


def load_head_meta(path) -> Dict[str, object]:
    """Registry metadata of a saved head: ``{"format_version", "kind",
    "backend", "quant"}``."""
    with np.load(Path(path)) as data:
        return _meta_from_archive(data)


def head_costs(cfg: SketchHeadConfig, d_model: int, vocab: int,
               *, quant: Optional[str] = None) -> dict:
    """Analytic memory/FLOP comparison vs the dense head (paper §4.3 model).

    ``dense_params`` / ``sketch_params`` count *elements* (the historical
    fields — identical under quantization, which is why they understate the
    storage win); ``dense_bytes`` / ``sketch_bytes`` / ``bytes_ratio`` are
    dtype-aware: f32 counts are 4 B, int8 counts 1 B, packed int4 counts
    ½ B (+ the (L, R) f32 scales), hash/transform params always f32.
    """
    _check_quant(quant)
    dense_params = d_model * vocab
    n_counts = cfg.n_rows * cfg.n_buckets * vocab
    aux_params = (d_model * cfg.proj_dim            # asymmetric transform A
                  + cfg.n_rows * cfg.k * cfg.proj_dim)  # hash bank w
    sketch_params = n_counts + aux_params
    dense_flops = 2 * d_model * vocab
    sketch_flops = (2 * d_model * cfg.proj_dim            # projection
                    + 2 * cfg.proj_dim * cfg.k * cfg.n_rows  # hashing
                    + cfg.n_rows * vocab)                 # gather-mean adds

    if quant == "int8":
        count_bytes = n_counts                            # 1 B/count
    elif quant == "int4":
        count_bytes = -(-cfg.n_rows // 2) * cfg.n_buckets * vocab  # ½ B
    else:
        count_bytes = 4 * n_counts
    scale_bytes = 4 * cfg.n_rows * cfg.n_buckets if quant else 0
    dense_bytes = 4 * dense_params
    sketch_bytes = count_bytes + scale_bytes + 4 * aux_params
    return {
        "dense_params": dense_params,
        "sketch_params": sketch_params,
        "param_ratio": dense_params / sketch_params,
        "dense_bytes": dense_bytes,
        "sketch_bytes": sketch_bytes,
        "bytes_ratio": dense_bytes / sketch_bytes,
        "dense_flops": dense_flops,
        "sketch_flops": sketch_flops,
        "flop_ratio": dense_flops / sketch_flops,
    }
