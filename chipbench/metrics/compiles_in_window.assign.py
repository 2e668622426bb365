"""``compiles_in_window.assign``: backend compiles inside the measured
window, from JAX's ``/jax/core/compile/backend_compile_duration`` events
(each new request row count compiles its result slices)."""


def read(ctx):
    return float(ctx["record"]["compiles_in_window"])
