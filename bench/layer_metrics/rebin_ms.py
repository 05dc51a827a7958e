"""Device milliseconds per step spent rebinning particles, averaged over
the chips.

``quadtree.rebuild_tree`` flattens the ``(n, n, s)`` slot grid and works on
one-dimensional arrays of all ``n * n * s`` slots: the argsort of box ids,
the gathers that permute positions and charges, and the scatters into the
new grid.  XLA fuses the gathers and scatters into fusions with generic
names, so the reader selects by result: the ops of the stepper's program
(``rk2_step``) whose every result is one-dimensional and at least as long
as the number of leaf boxes.  No FMM stage returns such an array; a look at
one level-8 trace found no other source."""


def _flat(op, boxes: int) -> bool:
    return bool(op.dims) and all(len(d) == 1 and d[0] >= boxes
                                 for d in op.dims)


def read(inputs):
    boxes = inputs.counts.size
    per_dev = [sum(o.dur for o in d.in_module(d.ops, "rk2_step")
                   if _flat(o, boxes))
               for d in inputs.trace.devices]
    if not any(per_dev):
        return None
    return sum(per_dev) / len(per_dev) / 1e6 / inputs.calls
