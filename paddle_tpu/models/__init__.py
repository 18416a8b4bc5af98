"""Model zoo covering the BASELINE configs (SURVEY.md §6)."""
from .llama import (  # noqa: F401
    LlamaConfig, LlamaForCausalLM, LlamaModel, LlamaDecoderLayer,
    build_hybrid_train_step,
)
from .jamba import (  # noqa: F401
    JambaConfig, JambaForCausalLM, JambaDecoderLayer, JambaMambaMixer,
    JambaAttention,
)
from .experts import RoutedExperts  # noqa: F401
from .mimo import (  # noqa: F401
    MiMoConfig, MiMoForCausalLM, MiMoDecoderLayer, MiMoAttention,
)
from .bert import (  # noqa: F401
    BertConfig, BertModel, BertForSequenceClassification, BertForPretraining,
    bert_pretraining_loss, ErnieConfig, ErnieModel,
    ErnieForSequenceClassification,
)
from .gpt import GPTConfig, GPTModel, GPTForCausalLM  # noqa: F401
from .deepfm import DeepFM  # noqa: F401
from .ocr import DBNet, CRNN, db_loss, ctc_rec_loss  # noqa: F401
from .detection import YOLOv3, TinyDarknet  # noqa: F401
