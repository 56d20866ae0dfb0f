"""Operations and bytes the algorithms need, computed from shapes. These
are the numerators of every utilization and roofline share the benchmark
reports; they live here so that no later PR can move them.

``cfg`` is a configuration file's ``config`` dict (GPT-style keys:
``vocab_size``, ``hidden_size``, ``num_layers``, ``num_heads``,
``intermediate_size``)."""


def gpt_matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix multiplication per token:
    the tied embedding/head matrix and each block's qkv, out and two MLP
    matrices. The learned position table is a lookup, not a matmul, and
    is left out (``models/gpt.py:gpt_flops_per_token`` counts it, 0.6 %
    high at these widths); biases and layer norms are left out too."""
    h = cfg["hidden_size"]
    ffn = cfg.get("intermediate_size") or 4 * h
    return cfg["vocab_size"] * h + cfg["num_layers"] * (4 * h * h + 2 * h * ffn)


def gpt_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model operations per trained token, forward and backward:
    ``6 * matmul_params + 12 * layers * hidden * seq_len``. The second
    term is attention's two batched matmuls (scores and values, 4 * seq *
    hidden a token a layer forward, three times that with the backward)
    over the full square, the PaLM paper's convention (appendix B) and
    the one ``gpt_flops_per_token`` uses; a causal mask needs half of it,
    which ``flash_*`` below count. Recomputed operations do not count."""
    return (6.0 * gpt_matmul_params(cfg)
            + 12.0 * cfg["num_layers"] * cfg["hidden_size"] * seq_len)


def flash_fwd_flops(batch: int, heads: int, seq: int, head_dim: int,
                    causal: bool = True) -> float:
    """Scores and values: two matmuls of 2 * seq * seq * head_dim each a
    head; a causal mask needs the lower triangle only."""
    full = 4.0 * batch * heads * seq * seq * head_dim
    return full / 2 if causal else full


def flash_bwd_flops(batch: int, heads: int, seq: int, head_dim: int,
                    causal: bool = True) -> float:
    """dV, dP, dQ and dK: four matmuls of the forward's size. Recomputing
    the scores (which the kernels do, twice) is not counted: it is how
    this algorithm saves memory, not what the mathematics needs."""
    return 2.0 * flash_fwd_flops(batch, heads, seq, head_dim, causal)


def flash_fwd_bytes(batch: int, heads: int, seq: int, head_dim: int,
                    itemsize: int = 2) -> float:
    """Read q, k, v and write o once each (the row statistics are a
    1/head_dim of that and ignored)."""
    return 4.0 * batch * heads * seq * head_dim * itemsize


def flash_bwd_bytes(batch: int, heads: int, seq: int, head_dim: int,
                    itemsize: int = 2) -> float:
    """Read q, k, v, o, do and write dq, dk, dv once each."""
    return 8.0 * batch * heads * seq * head_dim * itemsize


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """The least time the chip could take and which roof sets it."""
    t_compute = flops / peaks["flops_per_s"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    return ((t_compute, "compute") if t_compute >= t_memory
            else (t_memory, "memory"))
