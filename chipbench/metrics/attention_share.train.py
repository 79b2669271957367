"""Share of the traced device time that operations under the program's
``attention`` scope take: leaf operations only (a loop's body counts, the
loop itself does not), each under the scope XLA's ``tf_op`` gives it."""

from chipbench import program_spans


def read(*, trace, **_):
    if trace is None:
        return None
    ops = program_spans.leaf_ops(trace)
    inside = sum(d for d, scope in ops if program_spans.in_scope(scope, "attention"))
    return 100.0 * inside / sum(d for d, _ in ops) if inside else None
