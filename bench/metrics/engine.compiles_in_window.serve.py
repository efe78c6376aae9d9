"""JAX backend compilations during the measured window of a serving cell
(``/jax/core/compile/backend_compile_duration`` events); 0 when every
dispatch shape was warmed in set-up."""


def read(layer, peaks):
    return layer["compiles"] if layer.get("kind") == "serve" else None
