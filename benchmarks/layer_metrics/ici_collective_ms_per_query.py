"""Exchange (parallel/alltoall.py): device time of the collectives on the
busiest chip per traced query, self time of their operations in the trace.
XLA names an instruction that JAX asked for after the JAX primitive
(``all_to_all.41``, ``all_gather.3``, ``psum.2``, ``ppermute.1``:
underscores) and one the compiler put in after the HLO opcode
(``all-to-all.5``, ``all-reduce.1``: hyphens); the engine's exchange is of
the first kind, which ``collective_ms_per_query`` (it matches the opcode's
spelling against the instruction's name) does not see.  On the TPU's
operation line nothing runs beside a collective, so all of this time is
exposed.  Nothing to read without a trace, or on one chip."""

JAX_NAMED = ("all_to_all", "all_gather", "psum", "ppermute", "pmax", "pmin",
             "reduce_scatter")
COMPILER_NAMED = ("all-to-all", "all-gather", "all-reduce",
                  "collective-permute", "reduce-scatter",
                  "collective-broadcast", "ragged-all-to-all")


def is_collective(op: str) -> bool:
    """``op`` is ``<program>/<instruction> <result type>`` as the trace
    reduction keys an operation's self time."""
    name = op.split("/", 1)[-1].split(" ", 1)[0]
    stem = name.rstrip("0123456789").rstrip(".")
    return stem in JAX_NAMED or name.startswith(COMPILER_NAMED)


def read(run):
    if run.trace is None or not run.traced_times_ms or run.chips < 2:
        return None
    seconds = sum(s for op, s in run.trace.busiest.op_self_s.items()
                  if is_collective(op))
    return seconds * 1e3 / len(run.traced_times_ms)
