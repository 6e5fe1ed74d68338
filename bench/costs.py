"""Work that the algorithm needs, computed from shapes: operations and bytes
of the fused sketch-head kernel, of a logit head, and of a decode step.
What depends on the model family (its weights per token, parameters, KV
bytes and backbone operations) lives in the family's reference module,
``bench/reference/<family>.py``, so a new family arrives as one file.

Counts are of what the computation requires, not of what today's code
does: the count array is read once per call, however many batch tiles
re-read it, and attention reads only the live positions of each row.
"""

from __future__ import annotations


def fused_decode_cost(batch: int, d_model: int, head: dict,
                      vocab: int) -> dict:
    """Operations and bytes of one fused sketch-head call over ``batch``
    f32 hiddens.

    Bytes: the count array once (1 byte a count for int8, a half for
    int4, 4 for f32), its (L, R) f32 scales, the transform and hash bank,
    the hiddens read and the f32 logits written.  Operations: the
    projection (2 d d'), the hashes (2 L K d') and, per row, one
    scale-multiply-add over the vocabulary for each of the L rows.
    """
    n_rows, n_buckets, k, dp = (head["n_rows"], head["n_buckets"],
                                head["k"], head["proj_dim"])
    per_count = {"int8": 1.0, "int4": 0.5, None: 4.0}[head.get("quant")]
    counts = n_rows * n_buckets * vocab * per_count
    scales = 4 * n_rows * n_buckets if head.get("quant") else 0
    aux = 4 * (d_model * dp + n_rows * k * dp + n_rows * k)
    io = 4 * batch * (d_model + vocab)
    flops = batch * (2 * d_model * dp + 2 * n_rows * k * dp
                     + 2 * n_rows * vocab)
    return {"flops": float(flops), "bytes": float(counts + scales + aux + io),
            "count_bytes": float(counts)}


def head_flops(cfg: dict) -> float:
    """Operations of the logit head for one row."""
    head = cfg["head"]
    if head["kind"] == "dense":
        return 2.0 * cfg["d_model"] * cfg["vocab_size"]
    return fused_decode_cost(1, cfg["d_model"], head, cfg["vocab_size"])[
        "flops"]


def decode_flops(cfg: dict, ref, rows: int, live: int) -> float:
    """Operations of decoding ``rows`` tokens whose attention covers
    ``live`` positions in all (summed over the rows): the backbone's, from
    the family's reference module ``ref`` (``backbone_flops``), and the
    head's per row."""
    return ref.backbone_flops(cfg, rows, live) + rows * head_flops(cfg)
