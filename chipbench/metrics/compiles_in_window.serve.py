"""Programs lowered (compiled or read from the compile cache) inside the
traced window, from JAX's ``jaxpr_to_mlir_module`` monitoring events."""


def read(ctx):
    return ctx["compiles_in_window"]
