"""BCPNN core — the paper's contribution as composable JAX modules."""
from .hypercolumns import LayerGeom, encode_scalar_hcs, hc_hardmax, hc_softmax
from .traces import Traces, init_traces, mutual_information, update_traces, weights_from_traces
from .bcpnn_layer import (
    BACKENDS, Projection, ProjSpec, forward, init_projection, learn,
    maybe_rewire, normalize, rewire, support, topk_mask,
)
from .network import (
    BCPNNConfig,
    BCPNNState,
    DeepState,
    NetworkSpec,
    as_spec,
    hidden_rates,
    infer,
    init_deep,
    init_network,
    make_network_spec,
    online_learn_step,
    spec_from_dict,
    spec_to_dict,
    stack_rates,
    supervised_readout_step,
    supervised_step,
    train_projection_step,
    unsupervised_layer_step,
    unsupervised_step,
)
from .trainer import FitCursor, Trainer, eval_batches, evaluate_padded
from .head import (
    BCPNNHeadConfig,
    encode_features,
    head_predict,
    head_supervised,
    head_unsupervised,
    init_head,
)

__all__ = [
    "LayerGeom", "encode_scalar_hcs", "hc_hardmax", "hc_softmax",
    "Traces", "init_traces", "mutual_information", "update_traces", "weights_from_traces",
    "BACKENDS", "Projection", "ProjSpec", "forward", "init_projection",
    "learn", "maybe_rewire", "normalize", "rewire", "support", "topk_mask",
    "BCPNNConfig", "BCPNNState", "DeepState", "NetworkSpec", "as_spec",
    "hidden_rates", "infer", "init_deep", "init_network", "make_network_spec",
    "online_learn_step", "spec_from_dict", "spec_to_dict",
    "stack_rates", "supervised_readout_step", "supervised_step",
    "train_projection_step", "unsupervised_layer_step", "unsupervised_step",
    "FitCursor", "Trainer", "eval_batches", "evaluate_padded",
    "BCPNNHeadConfig", "encode_features", "head_predict", "head_supervised",
    "head_unsupervised", "init_head",
]
