"""Model zoo (parity: python/mxnet/gluon/model_zoo/vision + the reference's
example/ networks). `get_model("resnet50_v1")` mirrors mx model_zoo."""
from .lenet import LeNet, lenet
from .resnet import (get_resnet, resnet18_v1, resnet34_v1, resnet50_v1,
                     resnet101_v1, resnet152_v1, resnet18_v2, resnet34_v2,
                     resnet50_v2, resnet101_v2, resnet152_v2)
from .alexnet import AlexNet, alexnet
from .vgg import (VGG, get_vgg, vgg11, vgg13, vgg16, vgg19,
                  vgg11_bn, vgg13_bn, vgg16_bn, vgg19_bn)
from .mobilenet import (MobileNet, MobileNetV2, mobilenet1_0, mobilenet0_75,
                        mobilenet0_5, mobilenet0_25, mobilenet_v2_1_0,
                        mobilenet_v2_0_75, mobilenet_v2_0_5,
                        mobilenet_v2_0_25)
from .squeezenet import SqueezeNet, squeezenet1_0, squeezenet1_1
from .densenet import (DenseNet, densenet121, densenet161, densenet169,
                       densenet201)
from .inception import Inception3, inception_v3
from .bert import (BERTModel, BERTForPretrain, BERTPretrainLoss,
                   get_bert_model, bert_12_768_12, bert_24_1024_16)
from .ssd import (SSD, SSDLoss, ssd_512_resnet18_v1, ssd_512_resnet50_v1,
                  ssd_300_resnet18_v1)
from .transformer_lm import (TransformerLM, lm_loss, transformer_lm_small,
                             transformer_lm_base)
from .dlrm import DLRM, dlrm_loss, dlrm_small
from .moe_lm import (MoeLM, MoeLMCell, MTP, GroupedQueryAttentionCell,
                     LinearAttentionCell, LatentAttentionCell,
                     CompressedAttentionCell)

_MODELS = {}
for _name in ["resnet18_v1", "resnet34_v1", "resnet50_v1", "resnet101_v1",
              "resnet152_v1", "resnet18_v2", "resnet34_v2", "resnet50_v2",
              "resnet101_v2", "resnet152_v2", "lenet",
              "alexnet",
              "vgg11", "vgg13", "vgg16", "vgg19",
              "vgg11_bn", "vgg13_bn", "vgg16_bn", "vgg19_bn",
              "mobilenet1_0", "mobilenet0_75", "mobilenet0_5",
              "mobilenet0_25", "mobilenet_v2_1_0", "mobilenet_v2_0_75",
              "mobilenet_v2_0_5", "mobilenet_v2_0_25",
              "squeezenet1_0", "squeezenet1_1",
              "densenet121", "densenet161", "densenet169", "densenet201",
              "inception_v3",
              "bert_12_768_12", "bert_24_1024_16",
              "ssd_512_resnet18_v1", "ssd_512_resnet50_v1",
              "ssd_300_resnet18_v1",
              "transformer_lm_small", "transformer_lm_base",
              "dlrm_small"]:
    _MODELS[_name] = globals()[_name]


def get_model(name, **kwargs):
    name = name.lower()
    if name not in _MODELS:
        raise ValueError(f"unknown model {name!r}; available: {sorted(_MODELS)}")
    return _MODELS[name](**kwargs)


def register_model(name, fn):
    _MODELS[name.lower()] = fn
