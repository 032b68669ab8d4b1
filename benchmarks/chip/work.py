"""Operations and bytes that the served requests need, from the model's
sizes and the requests' lengths alone: not what an implementation happens
to do (padding, chunking, recomputation), so no change to the program can
move them.

A request with prompt length P and n generated tokens runs P prompt
positions (the last one also yields the first generated token) and n - 1
decode steps; decode step t = 1 .. n - 1 feeds token t - 1 at position
P + t - 1 and attends to P + t positions.
"""

BF16 = 2
F32 = 4


# -- dense decoder (keys of reference/dense.py) ------------------------------
def _dense_dims(m):
    return (m["num_hidden_layers"], m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"], m["intermediate_size"],
            m["vocab_size"])


def dense_layer_matmul_flops(m):
    """Weight-matmul FLOPs of one token through one layer."""
    _, d, hq, hkv, hd, ff, _ = _dense_dims(m)
    return 2 * (d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * ff)


def dense_attention_flops(m, ctx):
    """Score and value FLOPs of one query over ``ctx`` positions, all
    layers."""
    layers, _, hq, _, hd, _, _ = _dense_dims(m)
    return layers * 4 * hq * hd * ctx


def dense_request_flops(m, p, n):
    """Model FLOPs to serve one request: every prompt position and decode
    step through every layer, with attention over its own context, and the
    unembedding of the n positions whose logits choose a token."""
    layers, d, _, _, _, _, v = _dense_dims(m)
    positions = p + n - 1
    ctx_sum = positions * (positions + 1) // 2       # position i sees i + 1
    return (positions * layers * dense_layer_matmul_flops(m)
            + dense_attention_flops(m, 1) * ctx_sum + n * 2 * d * v)


def paged_decode_work(m, p, n):
    """(FLOPs, bytes) of the paged decode attention kernel over one
    request's n - 1 decode steps: each step reads the K/V of its P + t
    valid positions (bf16) and its query, and writes its output."""
    layers, _, hq, hkv, hd, _, _ = _dense_dims(m)
    ctx_sum = sum(p + t for t in range(1, n))
    kv_bytes = ctx_sum * layers * 2 * hkv * hd * BF16
    qo_bytes = (n - 1) * layers * 2 * hq * hd * BF16
    return dense_attention_flops(m, ctx_sum), kv_bytes + qo_bytes


def paged_prefill_work(m, p):
    """(FLOPs, bytes) of causal attention over one prompt, computed once:
    position i attends to i + 1 positions; Q, K, V read and the output
    written once (bf16)."""
    layers, _, hq, hkv, hd, _, _ = _dense_dims(m)
    flops = dense_attention_flops(m, p * (p + 1) // 2)
    return flops, layers * p * (2 * hq * hd + 2 * hkv * hd) * BF16


# -- Mamba-2 (keys of reference/ssm.py) ---------------------------------------
def _ssm_dims(m):
    d, n, hp, g = m["d_model"], m["d_state"], m["headdim"], m["ngroups"]
    di = m["expand"] * d
    return m["n_layer"], d, di, di // hp, hp, n, g, di + 2 * g * n


def ssm_layer_matmul_flops(m):
    """Projection and conv FLOPs of one token through one layer."""
    _, d, di, h, _, n, g, conv = _ssm_dims(m)
    return 2 * d * (2 * di + 2 * g * n + h) + 2 * m["d_conv"] * conv \
        + 2 * di * d


def ssd_scan_work(m, p):
    """(FLOPs, bytes) of the chunked SSD over one prompt of length p (a
    multiple of the chunk Q), all layers: per chunk and head the Q x Q
    scores C B^T (2 Q^2 N), their product with x (2 Q^2 P), the chunk state
    (2 Q N P) and its read-out (2 Q N P); x dt, the log decay, B, C (per
    group) read and y written once, in float32."""
    layers, _, _, h, hp, n, g, _ = _ssm_dims(m)
    q = m["chunk_size"]
    chunks = -(-p // q)
    flops = layers * h * chunks * (2 * q * q * (n + hp) + 4 * q * n * hp)
    nbytes = layers * p * (2 * h * hp + h + 2 * g * n) * F32
    return flops, nbytes


def ssm_request_flops(m, p, n):
    """Model FLOPs to serve one request: the prompt through the chunked
    SSD, n - 1 recurrent decode steps (state update and read-out, 4 N P per
    head), and the n unembeddings that choose tokens."""
    layers, d, _, h, hp, nst, _, _ = _ssm_dims(m)
    v = m["vocab_size"]
    steps = n - 1
    return ((p + steps) * layers * ssm_layer_matmul_flops(m)
            + ssd_scan_work(m, p)[0] + steps * layers * h * 4 * nst * hp
            + n * 2 * d * v)


def model_flops(family, m, p, n):
    if family == "dense":
        return dense_request_flops(m, p, n)
    if family == "ssm":
        return ssm_request_flops(m, p, n)
    raise ValueError(f"no FLOP count for family {family!r}")


def kernel_work(family, m, requests):
    """{kernel: (FLOPs, bytes)} summed over ``requests`` [(P, n)], for the
    kernels the family's serve path runs."""
    out = {}

    def add(name, fb):
        f, b = out.get(name, (0, 0))
        out[name] = (f + fb[0], b + fb[1])

    for p, n in requests:
        if family == "dense":
            add("paged_decode", paged_decode_work(m, p, n))
            add("paged_prefill", paged_prefill_work(m, p))
        elif family == "ssm":
            add("ssd_scan", ssd_scan_work(m, p))
    return out
