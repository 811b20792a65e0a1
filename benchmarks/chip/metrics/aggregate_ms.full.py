"""Device milliseconds per full-graph training step spent in the graph
aggregation ops: the union, inside the traced window, of the device ops
whose ``tf_op`` scope path (the program's ``jax.named_scope`` names) has
a component in ``SCOPES``, over the steps completed in the window.
Autodiff keeps a forward scope under ``jvp(...)`` and ``transpose(...)``,
so the backward pass counts too. XLA names a fusion by its root op, so
work fused into an op of another scope is not counted here."""

# the program's aggregation ops (``fnmatch`` patterns)
SCOPES = ("weighted_copy_reduce", "weighted_copy_reduce.bwd", "gspmm.*",
          "gsddmm.*", "edge_softmax", "fused_attention", "block_gspmm.*")


def read(run):
    from devtrace import scoped_ns
    steps = run.layer.get("steps")
    td = run.trace_data
    if not steps or td is None:
        return None
    ns = scoped_ns(td, SCOPES)
    if not ns:
        return None
    return ns * 1e-6 / steps
