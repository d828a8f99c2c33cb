"""``gluon.model_zoo.nlp`` — NLP models (GluonNLP capability parity).

Reference: the external GluonNLP package (dmlc/gluon-nlp) listed as a
capability target in SURVEY.md §2.4: BERT (pretrain+finetune), Transformer
MT with beam search, AWD-LSTM/standard LSTM language models, attention
cells.
"""
from .attention import *  # noqa: F401,F403
from .bert import *  # noqa: F401,F403
from .transformer import *  # noqa: F401,F403
from .language_model import *  # noqa: F401,F403
from .sampler import *  # noqa: F401,F403
from .llama import *  # noqa: F401,F403
from .decoder import *  # noqa: F401,F403
from .deepseek_v3 import *  # noqa: F401,F403
from .keye_vl2 import *  # noqa: F401,F403
from .kimi_linear import *  # noqa: F401,F403
from .sdar_moe import *  # noqa: F401,F403

from . import attention, bert, transformer, language_model, sampler, \
    llama, decoder, deepseek_v3, keye_vl2, kimi_linear, sdar_moe  # noqa

_MODELS = {}
for _m in (bert, transformer, language_model):
    for _name in _m.__all__:
        _fn = getattr(_m, _name)
        # model constructors only: lowercase factories, excluding the
        # parameterized get_* helpers and non-model utilities
        if callable(_fn) and _name[0].islower() and \
                not _name.startswith(("get_", "positional_")):
            _MODELS[_name] = _fn


def get_model(name, pretrained=False, root=None, ctx=None, **kwargs):
    """Reference: gluonnlp.model.get_model(name, pretrained=).

    ``pretrained=True`` resolves weights from the LOCAL model store
    (model_store.get_model_file; zero-egress build, no download)."""
    if name not in _MODELS:
        from ....base import MXNetError
        raise MXNetError(
            f"Model {name!r} is not present in the NLP model zoo; "
            f"available: {sorted(_MODELS)}")
    net = _MODELS[name](**kwargs)
    if pretrained:
        from ..model_store import get_model_file
        net.load_parameters(get_model_file(name, root), ctx=ctx)
    return net
