"""The least time a chip could take for a kernel call: the larger of its
operations over the peak rate and its bytes over the peak bandwidth."""


def least_seconds(flops, bytes_moved, peaks):
    """``(seconds, bound)``: ``bound`` says which peak sets the floor."""
    by_compute = flops / peaks["bf16_flops_per_s"]
    by_memory = bytes_moved / peaks["hbm_bytes_per_s"]
    if by_compute >= by_memory:
        return by_compute, "compute"
    return by_memory, "memory"
