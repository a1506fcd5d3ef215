"""Model zoo. Importing this package registers all 21 models (the reference
does the same in models/__init__.py:2-10)."""

from seist_tpu.models.losses import (  # noqa: F401
    BCELoss,
    BinaryFocalLoss,
    CELoss,
    CombinationLoss,
    FocalLoss,
    HuberLoss,
    MousaviLoss,
    MSELoss,
    TokenCELoss,
)

# Import model modules for their registration side effects.
from seist_tpu.models import (  # noqa: F401
    baz_network,
    distpt_network,
    ditingmotion,
    eqtransformer,
    magnet,
    nemotron_h,
    phasenet,
    seist,
)
from seist_tpu.models.api import (  # noqa: F401
    count_params,
    create_model,
    init_variables,
    param_shapes,
)
