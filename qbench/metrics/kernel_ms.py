"""Device milliseconds per application in the Pallas kernels
(tpu_custom_call ops) of the traced window."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or tr.apps == 0 or tr.kernel_s <= 0:
        return None
    return 1e3 * tr.kernel_s / tr.apps
