from . import lm
from .mobilenetv2 import mobilenet_v2, mobilenet_v2_paper, mobilenet_v2_smoke
from .mobilenetv3 import mobilenet_v3_large, mobilenet_v3_large_smoke

__all__ = ["lm", "mobilenet_v2", "mobilenet_v2_paper", "mobilenet_v2_smoke",
           "mobilenet_v3_large", "mobilenet_v3_large_smoke"]
