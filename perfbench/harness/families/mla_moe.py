"""DeepSeek-V3's layers (family ``mla_moe``), restated for the plain
reference from the written equations; ``a`` is the configuration's
``archs`` entry, and ``tp`` ranks share every layer.

Per layer, with ``M = batch * q_len``, ``hl = n_heads / tp`` heads on a
rank, ``r = kv_lora_rank``, ``dr = qk_rope_head_dim``:

- two RMSNorms on d for every layer, plus the norms of the q latent
  (``q_lora_rank``) and the kv latent (``r``); RoPE's elementwise work is
  left out, as in every family;
- latent attention: ``q_a`` d -> q_lora_rank and ``kv_a`` d -> r + dr on
  every rank (replicated), ``q_b`` q_lora_rank -> hl * (nope + dr);
  prefill decompresses (``kv_b`` r -> hl * (nope + v)), attends at q/k
  width nope + dr and v width v, and writes ``M * (r + dr)`` latent
  values; decode absorbs W_UK into the query (``batch x nope x r``, hl
  times), reads the whole latent cache once, runs the scores (``m = hl, k
  = r + dr, n = kv``) and the values (``m = hl, k = kv, n = r``) once a
  sequence with the cache left out of their bytes, then W_UV (``batch x r
  x v``, hl times) and the latent append; then ``o`` and the all-reduce;
- ``first_k_dense`` layers with a gated FFN of width ``d_ff``;
- the other layers: router and group top-k, the routed experts as one
  grouped GEMM over ``D`` distinct experts a rank serves (``D = E/tp * (1 -
  (1 - k/E)^M)`` under uniform routing, each with ``M * k / (tp * D)``
  rows), their activation, and the shared expert as a gated FFN whose
  all-reduce is the layer's combine.
"""
from harness.reference import BYTES, MATMUL, ffn


def distinct_experts(M, n_experts, top_k, ep):
    """Expected distinct experts of one rank's ``n_experts / ep`` that
    ``M`` tokens hit when each token picks ``top_k`` of ``n_experts``
    uniformly."""
    return n_experts / ep * (1.0 - (1.0 - top_k / n_experts) ** M)


def _latent_attention(g, a, batch, q_len, kv_len, tp, count, decode):
    d = a["d_model"]
    hl = max(1, a["n_heads"] // tp)
    M = batch * q_len
    r, dr, nope = a["kv_lora_rank"], a["qk_rope_head_dim"], \
        a["qk_nope_head_dim"]
    v, q_rank = a["v_head_dim"], a["q_lora_rank"]
    width = r + dr
    g.vector(M * q_rank, 8.0, count=count)
    g.vector(M * r, 8.0, count=count)
    g.matmul(M, d, q_rank, count=count)
    g.matmul(M, q_rank, hl * (nope + dr), count=count)
    g.matmul(M, d, width, count=count)
    if decode:
        g.matmul(batch, nope, r, count=count * hl)
        g.memcpy(batch * kv_len * width * BYTES, count=count)
        g.op(MATMUL, 2.0 * hl * width * kv_len, hl * (width + kv_len) * BYTES,
             hl, kv_len, width, count=count * batch)
        g.vector(batch * hl * kv_len, 6.0, count=count)
        g.op(MATMUL, 2.0 * hl * kv_len * r, hl * (kv_len + r) * BYTES,
             hl, r, kv_len, count=count * batch)
        g.matmul(batch, r, v, count=count * hl)
        g.memcpy(batch * width * BYTES, count=count)
    else:
        g.matmul(M, r, hl * (nope + v), count=count)
        g.matmul(q_len, nope + dr, kv_len, count=count * batch * hl)
        g.vector(batch * hl * q_len * kv_len, 6.0, count=count)
        g.matmul(q_len, kv_len, v, count=count * batch * hl)
        g.memcpy(M * width * BYTES, count=count)
    g.matmul(M, hl * v, d, count=count)
    g.allreduce(M * d, count=count)


def _distinct_expert_moe(g, a, M, tp, count):
    d, E, k, ff = a["d_model"], a["n_experts"], a["top_k"], a["expert_ff"]
    D = distinct_experts(M, E, k, tp)
    rows = M * k / (tp * D)
    g.matmul(M, d, E, count=count)
    g.vector(M * E, 4.0, count=count)
    g.matmul(rows, d, 2 * ff, count=count * D)
    g.vector(M * k / tp * ff, 8.0, count=count)
    g.matmul(rows, ff, d, count=count * D)
    ffn(g, M, d, ff * a["n_shared_experts"], tp, True, count)


def layers(g, a, batch, q_len, kv_len, tp, decode):
    d, L = a["d_model"], a["n_layers"]
    M = batch * q_len
    n_dense = min(a["first_k_dense"], L)
    g.vector(2 * M * d, 8.0, count=L)
    _latent_attention(g, a, batch, q_len, kv_len, tp, L, decode)
    if n_dense:
        ffn(g, M, d, a["d_ff"], tp, True, n_dense)
    if L > n_dense:
        _distinct_expert_moe(g, a, M, tp, L - n_dense)
