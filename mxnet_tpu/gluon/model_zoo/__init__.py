from . import vision
from .vision import get_model
from . import bert
from .bert import (BERTModel, BERTMLMHead, BERTNSPHead, bert_base,
                   bert_large, bert_serving_entry, get_bert)
from . import kimi_linear as kimi_linear_zoo
from .kimi_linear import KimiLinearModel, kimi_linear
from . import phi4_flash as phi4_flash_zoo
from .phi4_flash import Phi4FlashModel, phi4_flash
from . import keye_vl2 as keye_vl2_zoo
from .keye_vl2 import KeyeVL2Model, keye_vl2
from . import joyai_llm_flash as joyai_llm_flash_zoo
from .joyai_llm_flash import JoyAILLMFlashModel, joyai_llm_flash
from . import sdar as sdar_zoo
from .sdar import SDARModel, sdar
from . import wide_deep as wide_deep_zoo
from .wide_deep import WideDeep, wide_deep
