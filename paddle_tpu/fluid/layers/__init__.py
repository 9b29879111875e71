"""fluid.layers — op wrapper namespace (reference:
`python/paddle/fluid/layers/`)."""
from . import nn, tensor, loss, collective, math_op_patch  # noqa: F401
from . import control_flow  # noqa: F401
from . import distributions  # noqa: F401
from . import rnn_decode  # noqa: F401
from .rnn_decode import (  # noqa: F401
    RNNCell, GRUCell, BeamSearchDecoder, dynamic_decode,
)


def beam_search(pre_ids, pre_scores, ids, scores, beam_size, end_id,
                level=0, is_accumulated=True, name=None,
                return_parent_idx=False):
    """reference layers/rnn.py beam_search op wrapper."""
    from ..layer_helper import apply_op

    outs = apply_op("beam_search", "beam_search",
                    {"pre_ids": [pre_ids], "pre_scores": [pre_scores],
                     "ids": [ids], "scores": [scores]},
                    {"beam_size": beam_size, "end_id": end_id,
                     "level": level, "is_accumulated": is_accumulated},
                    ["selected_ids", "selected_scores", "parent_idx"])
    if return_parent_idx:
        return outs[0], outs[1], outs[2]
    return outs[0], outs[1]


def beam_search_decode(ids, scores, beam_size, end_id, name=None):
    from ..layer_helper import apply_op

    outs = apply_op("beam_search_decode", "beam_search_decode",
                    {"Ids": [ids], "Scores": [scores]},
                    {"beam_size": beam_size, "end_id": end_id},
                    ["SentenceIds", "SentenceScores"])
    return outs[0], outs[1]


def gather_tree(ids, parents):
    from ..layer_helper import apply_op

    return apply_op("gather_tree", "gather_tree",
                    {"Ids": [ids], "Parents": [parents]}, {}, ["Out"],
                    out_dtype="int64")[0]
from . import learning_rate_scheduler  # noqa: F401
from .nn import *  # noqa: F401,F403
from .nn_extra import *  # noqa: F401,F403
from .hybrid import *  # noqa: F401,F403
from . import nn_extra  # noqa: F401
from . import detection  # noqa: F401
from .detection import *  # noqa: F401,F403
from .tensor import *  # noqa: F401,F403
from .loss import *  # noqa: F401,F403
from .control_flow import (  # noqa: F401
    Scan, While, while_loop, cond, case, switch_case, increment,
    less_than, less_equal, greater_than, greater_equal, equal, not_equal,
    Print, Assert, StaticRNN, is_empty, reorder_lod_tensor_by_rank,
)
from .learning_rate_scheduler import (  # noqa: F401
    noam_decay, exponential_decay, natural_exp_decay, inverse_time_decay,
    polynomial_decay, piecewise_decay, cosine_decay, linear_lr_warmup,
)

# `data` also lives at layers top level in the reference
from .tensor import data  # noqa: F401
