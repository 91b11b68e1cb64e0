"""Share of its bytes' bound that the delta-rule layers reach in a whole
execution of ``program`` (a job's whole decode): 100 x the bytes they
have to move a job over their device time at the chip's HBM bandwidth.

The bytes are the recurrent state and conv tails they read and write
(the program's counter ``state_bytes``, over the window's jobs) and their
weights once a decode step (the kind's ``recurrence_weight_bytes``; for
the ``textgen`` kind, which has none, its own count of a KDA layer's
projections, ``textgen._weights``).

The time is the operations under the scope ``recurrence`` plus XLA's
own operations on the float32 states, which carry no ``op_name``: the
compiler keeps a layer's (rows, heads, D, D) state in the core's fast
memory while the scope's fusion updates it, and moves it to and from HBM
by asynchronous copies and slices outside the scope (``copy-start`` /
``copy-done``, ``slice-start`` / ``slice-done``, the custom call that
joins the slices). Left out, the scope alone reads over 100 on the Ling
cell (115%: the state's traffic was not in its time). Each counted byte
is moved at least once inside that time, so the share cannot pass 100.
Per whole execution (``program_whole``'s rule), the median. None
without a trace, off the chip, where the program carries no such scope
or counter (a program whose delta-rule layers do not count their state:
the parent's), or for a kind without delta-rule layers."""
import bisect
import statistics

from perfbench import hlo, scopes, trace
from perfbench.readers import counter_ratio, program_whole, scope_device

ITEM = 2                                                # bfloat16


def weight_bytes(context) -> float | None:
    """Bytes of the delta-rule layers' weights, read once a step."""
    own = getattr(context.kind, "recurrence_weight_bytes", None)
    if own is not None:
        return own(context.config)
    if context.config["kind"] != "textgen":
        return None
    from perfbench.kinds import textgen

    w = textgen._weights(context.config)
    return w["layers"][0] * w["kda"] * ITEM


def state_dims(config: dict) -> tuple[int, int, int]:
    """(heads, D, D) of a delta-rule layer's float32 state."""
    linear = config.get("linear_attn_config")
    if linear is not None:
        return linear["num_heads"], linear["head_dim"], linear["head_dim"]
    return (config["num_attention_heads"], config["head_dim"],
            config["head_dim"])


def recurrence_ms(context, program, scope) -> float | None:
    """Median over whole executions of ``program`` of the device ms of
    the operations under ``scope`` and of the unscoped operations whose
    first result is a float32 delta-rule state."""
    joined = scope_device._joined(context)
    if joined is None or not scopes.carries(joined[1], program, scope):
        return None
    device, maps, modules = joined
    ops = maps.get(program, {})
    dims = state_dims(context.config)
    device = sorted(device, key=lambda event: event[1])
    starts = [start for _, start, _ in device]
    found = []
    for lo, hi in program_whole.whole_executions(modules, program):
        ms = 0.0
        for name, start, dur in device[bisect.bisect_left(starts, lo):
                                       bisect.bisect_left(starts, hi)]:
            if start + dur > hi or trace.is_container(trace.op_name(name)):
                continue
            carried = scopes.event_scopes(ops, name) or frozenset()
            shape = hlo.result_shape(name)
            if scope in carried or (
                    not carried and shape is not None and shape[0] == "f32"
                    and tuple(shape[1][-3:]) == dims
                    and len(shape[1]) == 4):
                ms += dur * 1e-6
        found.append(ms)
    return statistics.median(found) if found else None


def read(context, program, scope, state_bytes):
    if not context.good:
        return None
    moved = counter_ratio.delta(context, state_bytes["family"],
                                state_bytes["labels"])
    weights = weight_bytes(context)
    if not moved or weights is None:
        return None
    ms = recurrence_ms(context, program, scope)
    if not ms:
        return None
    jobs = [context.ran["sent"][s["id"]]["job"] for s in context.good]
    steps = sum(job["max_new_tokens"] - 1 for job in jobs) / len(jobs)
    per_job = moved / len(jobs) + weights * steps
    return 100.0 * per_job / (ms * 1e-3 * context.peaks["hbm_gbps"] * 1e9)
