"""Device self time per step of the ops under the named scope ``loss``
(the chunk function of ``models/transformer.lm_loss``): the unembedding
and the cross-entropy, forward, backward and remat, without the forward
pass it follows. Mean over the cell's devices; ``None`` where the step has
no such scope."""


def read(ctx):
    t = ctx["trace"]
    s = t.get("scope_s", {}).get("loss", 0.0)
    return 1e3 * s / t["steps"] if s > 0 else None
