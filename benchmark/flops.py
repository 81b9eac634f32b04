"""Operations and bytes of a kernel, counted from shapes. One multiply-add
is 2 FLOPs. What a whole configuration requires per row is counted beside
its plain reference (``references/<family>.py: flops_per_row``)."""


def attention_flops(batch, heads, seq, head_dim, causal):
    """(forward, backward) FLOPs of one attention call: 2 matrix products
    of s x s x d per head forward, 4 backward; causal attention at the
    half of the score matrix that the mask keeps. No recomputation."""
    product = 2 * batch * heads * seq * seq * head_dim
    if causal:
        product //= 2
    return 2 * product, 4 * product


def attention_bytes(batch, heads, seq, head_dim, itemsize=2):
    """(forward, backward) bytes that must cross HBM: q, k, v read and o
    written; then q, k, v, o, do read and dq, dk, dv written."""
    tensor = batch * heads * seq * head_dim * itemsize
    return 4 * tensor, 8 * tensor
