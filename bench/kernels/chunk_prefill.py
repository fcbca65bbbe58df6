"""Chunked-prefill attention (``repro.kernels.chunk_prefill``): a chunk of
prompt tokens at positions [a, b) attends causally to the b tokens written
so far."""

NAMES = ("chunk_prefill_attention",)


def cost(n_heads: int, n_kv_heads: int, head_dim: int, start: int,
         end: int, dtype_bytes: int = 2):
    """(flops, bytes) of one chunk of one layer: query t sees t + 1 keys;
    the b visible keys' K and V are read once, q read and the output
    written."""
    pairs = (end * (end + 1) - start * (start + 1)) // 2
    flops = 4 * n_heads * head_dim * pairs
    nbytes = (2 * end * n_kv_heads * head_dim
              + 2 * (end - start) * n_heads * head_dim) * dtype_bytes
    return flops, nbytes


def prompt_chunks(start: int, end: int, chunk: int):
    """The [a, b) chunks in which the engine runs prompt positions
    [start, end): fixed-width chunks from the first uncached position."""
    return [(a, min(a + chunk, end)) for a in range(start, end, chunk)]
