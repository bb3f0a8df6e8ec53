"""Share of the window's fragments that ran a fused kernel
(``PipelineReport.kernel_fragments`` over ``n_fragments``)."""


def read(run):
    frags = sum(r.get("fragments", 0) for r in run.records)
    kern = sum(r.get("kernel_fragments", 0) for r in run.records)
    return 100.0 * kern / frags if frags else None
