"""MXU products and collectives the train step runs AGAIN in its
recomputed layer bodies: the instructions of the optimized
`jit_train_step` that the program's own index places in phase `remat`
and that are a fusion holding a `dot` or a `convolution`, such an
instruction unfused, or an `all-reduce`, `all-gather`, `reduce-scatter`,
`collective-permute` or `all-to-all` (start forms included), by
`paddle_tpu.jit.aot.products`. A count of the program as compiled (the
layer scan's body is compiled once): the same in every run of a cell.
Its floor is 0 on one chip and 2 on a ZeRO mesh: there the step built
again after its first call (the parameters come back sharded) gathers
the two norms' parameters in the recomputed body under every policy,
two all-gathers of 2 x hidden values a layer that are no product.
None where the program keeps no such record or built no train step."""


def read(run):
    from paddle_tpu.jit import aot
    products = getattr(aot, "products", None)
    found = products("jit_train_step", phase="remat") if products else None
    return None if found is None else float(len(found))
