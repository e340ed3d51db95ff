"""Settings shared by the Pallas TPU kernels of this package."""
import jax
from jax.experimental.pallas import tpu as pltpu


def expand_grid_params():
    """Compiler params shared by every tile-expansion kernel (fused_expand,
    fused_expand_q, lt_select_expand): a sequential ("arbitrary") grid, so
    the revisiting accumulation over dst-sorted tiles is legal."""
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def checked_interpret(interpret: bool) -> bool:
    """``interpret``, refused on a TPU backend: there a kernel runs compiled
    or not at all (`kernels.ops._interpret` picks the mode per backend)."""
    if interpret and jax.default_backend() == "tpu":
        raise ValueError("Pallas interpret mode requested on a TPU backend; "
                         "pass interpret=kernels.ops._interpret()")
    return interpret
