"""Layer type implementations.

TPU-native analog of paddle/gserver/layers/ (95 registered types, SURVEY
A.1). Importing this package registers every layer type into
LAYER_REGISTRY; the public user-facing wrappers live in paddle_tpu.layer.
"""

from paddle_tpu.layers import basic       # noqa: F401
from paddle_tpu.layers import cost        # noqa: F401
from paddle_tpu.layers import math_ops    # noqa: F401
from paddle_tpu.layers import conv        # noqa: F401
from paddle_tpu.layers import norm        # noqa: F401
from paddle_tpu.layers import sequence    # noqa: F401
from paddle_tpu.layers import recurrent   # noqa: F401
from paddle_tpu.layers import recurrent_group  # noqa: F401
from paddle_tpu.layers import crf_ctc     # noqa: F401
from paddle_tpu.layers import attention   # noqa: F401
from paddle_tpu.layers import delta_net   # noqa: F401
from paddle_tpu.layers import short_conv  # noqa: F401
from paddle_tpu.layers import moe         # noqa: F401
from paddle_tpu.layers import diffusion   # noqa: F401
from paddle_tpu.layers import detection   # noqa: F401
from paddle_tpu.layers import misc        # noqa: F401
