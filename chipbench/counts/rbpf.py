"""Work of one generation of the RBPF filter over a block store."""

from __future__ import annotations

from typing import Tuple

# state per particle: xi, m (2), P (2x2); all float32
STATE_WORDS = 1 + 2 + 4
FLOAT_BYTES = 4
INDEX_BYTES = 4
# per particle: propagation, two Kalman updates, the likelihood, and the
# systematic resampling's weight pass (a rough operation count; the step
# is bound by bytes, not operations)
FLOPS_PER_PARTICLE = 150


def generation_work(n: int, max_blocks: int, record: int) -> Tuple[float, float]:
    """``(flops, bytes)`` of one generation for ``n`` particles: the state
    read and written, the weights read and written, the record appended,
    and the block-table rows read and written by the resampling gather."""
    state = 2 * n * STATE_WORDS * FLOAT_BYTES
    weights = 2 * n * FLOAT_BYTES
    append = n * record * FLOAT_BYTES
    tables = 2 * n * max_blocks * INDEX_BYTES
    return float(n * FLOPS_PER_PARTICLE), float(state + weights + append + tables)
