"""Operations and bytes that the served work needs, from shapes.

These are the algorithm's needs, not what the program happens to do:
padding rows, padded prompt buckets, logits of positions nobody reads
and blocks read past a sequence's end are not counted, so a program
that wastes less moves closer to the roofline. One multiply-add is two
operations. ``dm`` is a ``configs.dense_decoder.Dims``.
"""

from __future__ import annotations

BF16 = 2


def layer_matmul_params(dm) -> int:
    """Weights one token passes through in one layer's linear maps."""
    q = dm.n_heads * dm.head_dim
    kv = dm.n_kv_heads * dm.head_dim
    return dm.d_model * (2 * q + 2 * kv) + 3 * dm.d_model * dm.d_ff


def attention_flops(dm, query_key_pairs: int) -> int:
    """QK^T and PV over ``query_key_pairs`` (query, key) pairs per head,
    summed over the layers."""
    return 4 * dm.n_layers * dm.n_heads * dm.head_dim * query_key_pairs


def paged_decode(dm, live_token_steps: int, slot_steps: int):
    """(operations, bytes) of the paged decode kernel over a run of
    steps: ``live_token_steps`` is the sum over steps and decoding slots
    of the tokens each slot attends to (its new token included), and
    ``slot_steps`` the sum over steps of the decoding slots. Bytes are
    the keys and values read, and each query read and output written."""
    flops = attention_flops(dm, live_token_steps)
    kv = live_token_steps * 2 * dm.n_kv_heads * dm.head_dim * BF16
    qo = slot_steps * 2 * dm.n_heads * dm.head_dim * BF16
    return flops, dm.n_layers * (kv + qo)


def flash_prefill(dm, lengths):
    """(operations, bytes) of causal prefill attention over prompts of
    ``lengths`` tokens: queries, keys, values read and outputs written
    once."""
    pairs = sum(s * (s + 1) // 2 for s in lengths)
    width = 2 * (dm.n_heads + dm.n_kv_heads) * dm.head_dim * BF16
    return attention_flops(dm, pairs), dm.n_layers * sum(lengths) * width


def decode_step_flops(dm, live_token_steps: int, slot_steps: int) -> int:
    """Model operations of decode steps: every decoding slot's token
    through every layer and the output head, and its attention."""
    per_token = 2 * (dm.n_layers * layer_matmul_params(dm)
                     + dm.d_model * dm.vocab_size)
    return per_token * slot_steps + attention_flops(dm, live_token_steps)


def prefill_flops(dm, lengths) -> int:
    """Model operations of prefilling prompts of ``lengths`` tokens: every
    token through every layer, the output head for the last position of
    each prompt, and causal attention."""
    tokens = sum(lengths)
    return (2 * dm.n_layers * layer_matmul_params(dm) * tokens
            + 2 * dm.d_model * dm.vocab_size * len(lengths)
            + flash_prefill(dm, lengths)[0])


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> float | None:
    """Least time the chip could take (the larger of operations over peak
    rate and bytes over peak bandwidth) over ``seconds``, in percent.
    None where nothing was timed."""
    if seconds <= 0:
        return None
    bound = max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * bound / seconds


def mfu(flops: float, seconds: float, peak: dict) -> float | None:
    """Operations over (seconds x peak rate), in percent."""
    if seconds <= 0:
        return None
    return 100.0 * flops / (seconds * peak["bf16_flops"])
