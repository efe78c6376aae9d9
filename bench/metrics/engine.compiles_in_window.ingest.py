"""JAX backend compilations during the measured window of an ingest cell
(``/jax/core/compile/backend_compile_duration`` events); 0 when every
shape was warmed in set-up."""


def read(layer, peaks):
    return layer["compiles"] if layer.get("kind") == "ingest" else None
