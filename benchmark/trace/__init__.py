"""From a ``jax.profiler`` trace to numbers: the reduction every PR is measured by."""
