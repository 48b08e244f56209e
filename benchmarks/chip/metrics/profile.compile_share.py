"""Per cent of the profile window in JAX's compile pipeline (trace, lower,
compile, load from the persistent cache), from jax.monitoring."""
from chipbench.readers import compile_share as read  # noqa: F401
