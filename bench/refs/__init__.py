"""Plain references the comparison that decides ``correct`` runs against.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``, written
from the published equations; they import nothing of the program and take
nothing it made. Each can also run in the precision one step below the one
its configuration states, which is the control the comparison must fail.
"""
