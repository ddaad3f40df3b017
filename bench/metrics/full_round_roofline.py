"""Share of its roofline that the full certified round
(``core/solver._screen_round``) reaches on the device, in percent.

Each execution's least time is the larger of its operations over the
peak rate and its bytes over the peak bandwidth, with the operations and
bytes of the round's pass over X: 2 n p operations (X^T r), and X read
once at 2 bytes an entry, the narrowest format any implementation of the
round reads.  The share is the sum of those least times over the summed
device time of the executions, so it cannot pass 100% whatever the
precision the round runs in."""

PROGRAM = r"^jit__screen_round$"


def flops(n: int, p: int) -> float:
    return 2.0 * n * p


def bytes_moved(n: int, p: int) -> float:
    return 2.0 * n * p


def least_seconds(n: int, p: int, peaks: dict) -> float:
    return max(flops(n, p) / peaks["flops_per_s"],
               bytes_moved(n, p) / peaks["bytes_per_s"])


def read(ctx):
    if not ctx["trace_complete"]:      # cut short: nothing to read
        return None
    runs, seconds = ctx["trace"].device_seconds(PROGRAM)
    if not runs:
        return None
    n, p = ctx["shape"]
    return 100.0 * runs * least_seconds(n, p, ctx["peaks"]) / seconds
