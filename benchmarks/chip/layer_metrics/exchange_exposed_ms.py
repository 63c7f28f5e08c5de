"""Device idle milliseconds a step while the program's hvd.allreduce_gradients span is open; notes the table of that idle time by innermost program span of either thread."""
from chipbench import program_spans, readers

LAYER = "Eager adapter and cycle"
UNIT = "ms"
MOVES = "images_per_s_chip"


def read(ctx):
    table = program_spans.idle_under(ctx, 'hvd.allreduce_gradients')
    if table is None:
        return None
    rows = sorted(table.items(), key=lambda kv: -kv[1])
    whole = program_spans.span_ms_per_step(ctx, 'hvd.allreduce_gradients')
    children = program_spans.span_ms_per_step(
        ctx, 'hvd.enqueue', 'hvd.synchronize')
    ctx["notes"].append(
        f"hvd.allreduce_gradients is {whole} ms a step, its children "
        f"on the main thread (hvd.enqueue, hvd.synchronize) "
        f"{children}; device idle ms a step while it is open, by the "
        f"innermost span of the main thread | of the other threads: "
        + "; ".join(f"{main} | {other} = "
                    f"{readers.per_step_ms(ctx, ns / 1e9):.3f}"
                    for (main, other), ns in rows))
    return readers.per_step_ms(ctx, sum(table.values()) / 1e9)
