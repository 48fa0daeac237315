"""Operations and bytes of the benchmark's work, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change how
its work is counted: :mod:`bench.counts.vision` for the paper's residual
networks and the ghost batch norm kernels, :mod:`bench.counts.lm` for the
decoder language models, their flash-attention kernel and serving.
"""


def least_seconds(flops: float, bytes_: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute bound
    and the memory bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_ / peaks["hbm_bytes_per_s"])
