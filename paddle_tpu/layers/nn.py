"""Neural-net layer functions (reference python/paddle/fluid/layers/nn.py)."""
from __future__ import annotations

import numpy as np

from ..framework.dtype import convert_dtype, dtype_name
from ..layer_helper import LayerHelper, ParamAttr
from .. import initializer as init_mod

__all__ = [
    "data", "fc", "conv2d", "conv2d_transpose", "pool2d", "batch_norm",
    "layer_norm", "group_norm", "instance_norm", "dropout", "embedding",
    "relu", "sigmoid", "tanh", "softmax", "log_softmax", "gelu", "leaky_relu",
    "elementwise_add", "elementwise_sub", "elementwise_mul", "elementwise_div",
    "elementwise_max", "elementwise_min", "elementwise_pow",
    "matmul", "mul", "reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
    "reduce_prod", "mean", "scale", "cast", "reshape", "transpose", "concat",
    "split", "stack", "unstack", "squeeze", "unsqueeze", "flatten", "slice",
    "gather", "gather_nd", "scatter", "expand", "one_hot", "topk", "argmax",
    "argmin", "argsort", "accuracy", "auc", "clip", "clip_by_norm", "sums",
    "elementwise_mod", "elementwise_floordiv", "l2_normalize", "pad", "pad2d",
    "image_resize", "resize_nearest", "resize_bilinear", "relu6",
    "softplus", "swish", "hard_swish", "hard_sigmoid", "exp", "sqrt", "abs",
    "square", "log", "floor", "ceil", "round", "sign", "pow", "cos", "sin",
    "hsigmoid", "edit_distance", "bilinear_tensor_product",
    "add_position_encoding", "cos_sim",
    "equal", "not_equal", "less_than", "less_equal", "greater_than",
    "greater_equal", "logical_and", "logical_or", "logical_not", "logical_xor",
    "where", "cond_take", "unique", "cumsum", "prelu", "brelu",
    "fused_attention", "switch_moe", "routed_moe", "rms_norm",
    "rotary_embedding", "swiglu", "relu2", "causal_conv1d",
    "gated_short_conv", "ssm_scan",
    "gated_group_rms_norm", "l2_norm", "head_gate", "kda_gate", "kda_scan",
    "detach", "sparse_index", "sparse_index_loss",
]


def data(name, shape, dtype="float32", lod_level=0, append_batch_size=True,
         stop_gradient=True):
    """Declare an input variable (reference layers/data_feeder/data op).

    append_batch_size=True prepends a -1 batch dim (fluid 1.x convention).
    """
    helper = LayerHelper("data")
    full_shape = list(shape)
    if append_batch_size and (not full_shape or full_shape[0] != -1):
        full_shape = [-1] + full_shape
    block = helper.main_program.global_block()
    return block.create_var(name=name, shape=full_shape,
                            dtype=convert_dtype(dtype), is_data=True,
                            stop_gradient=stop_gradient)


def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, name=None):
    """Fully-connected (reference layers/nn.py fc → mul + elementwise_add)."""
    helper = LayerHelper("fc")
    in_shape = input.shape
    in_features = int(np.prod([d for d in in_shape[num_flatten_dims:]]))
    w = helper.create_parameter(param_attr, [in_features, size],
                                dtype=dtype_name(input.dtype))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("mul", inputs={"X": [input], "Y": [w]},
                     outputs={"Out": [out]},
                     attrs={"x_num_col_dims": num_flatten_dims,
                            "y_num_col_dims": 1})
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [size],
                                    dtype=dtype_name(input.dtype), is_bias=True)
        tmp = helper.create_variable_for_type_inference(input.dtype)
        helper.append_op("elementwise_add", inputs={"X": [out], "Y": [b]},
                         outputs={"Out": [tmp]},
                         attrs={"axis": num_flatten_dims})
        out = tmp
    return helper.append_activation(out, act)


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, act=None,
           use_cudnn=True, name=None, data_format="NCHW"):
    helper = LayerHelper("conv2d")
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    stride = [stride, stride] if isinstance(stride, int) else list(stride)
    padding = [padding, padding] if isinstance(padding, int) else list(padding)
    dilation = [dilation, dilation] if isinstance(dilation, int) else list(dilation)
    c_in = input.shape[1]
    groups = groups or 1
    w_shape = [num_filters, c_in // groups] + list(filter_size)
    fan_in = (c_in // groups) * filter_size[0] * filter_size[1]
    default_init = init_mod.Normal(0.0, (2.0 / fan_in) ** 0.5)
    w = helper.create_parameter(param_attr, w_shape,
                                dtype=dtype_name(input.dtype),
                                default_initializer=default_init)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("conv2d",
                     inputs={"Input": [input], "Filter": [w]},
                     outputs={"Output": [out]},
                     attrs={"strides": stride, "paddings": padding,
                            "dilations": dilation, "groups": groups})
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [num_filters],
                                    dtype=dtype_name(input.dtype), is_bias=True)
        tmp = helper.create_variable_for_type_inference(input.dtype)
        helper.append_op("elementwise_add", inputs={"X": [out], "Y": [b]},
                         outputs={"Out": [tmp]}, attrs={"axis": 1})
        out = tmp
    return helper.append_activation(out, act)


def conv2d_transpose(input, num_filters, filter_size, stride=1, padding=0,
                     dilation=1, param_attr=None, bias_attr=None, act=None,
                     name=None):
    helper = LayerHelper("conv2d_transpose")
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    stride = [stride, stride] if isinstance(stride, int) else list(stride)
    padding = [padding, padding] if isinstance(padding, int) else list(padding)
    dilation = [dilation, dilation] if isinstance(dilation, int) else list(dilation)
    c_in = input.shape[1]
    w = helper.create_parameter(param_attr, [c_in, num_filters] + filter_size,
                                dtype=dtype_name(input.dtype))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("conv2d_transpose",
                     inputs={"Input": [input], "Filter": [w]},
                     outputs={"Output": [out]},
                     attrs={"strides": stride, "paddings": padding,
                            "dilations": dilation})
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [num_filters],
                                    dtype=dtype_name(input.dtype), is_bias=True)
        tmp = helper.create_variable_for_type_inference(input.dtype)
        helper.append_op("elementwise_add", inputs={"X": [out], "Y": [b]},
                         outputs={"Out": [tmp]}, attrs={"axis": 1})
        out = tmp
    return helper.append_activation(out, act)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1, pool_padding=0,
           global_pooling=False, ceil_mode=False, exclusive=True, name=None,
           adaptive=False):
    helper = LayerHelper("pool2d")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("pool2d", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"pooling_type": pool_type,
                            "ksize": [pool_size, pool_size] if isinstance(pool_size, int) else list(pool_size),
                            "strides": [pool_stride, pool_stride] if isinstance(pool_stride, int) else list(pool_stride),
                            "paddings": [pool_padding, pool_padding] if isinstance(pool_padding, int) else list(pool_padding),
                            "global_pooling": global_pooling,
                            "exclusive": exclusive, "adaptive": adaptive})
    return out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               name=None, moving_mean_name=None, moving_variance_name=None,
               use_global_stats=False):
    helper = LayerHelper("batch_norm")
    c = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    dtype = "float32"
    scale = helper.create_parameter(param_attr, [c], dtype=dtype,
                                    default_initializer=init_mod.Constant(1.0))
    bias = helper.create_parameter(bias_attr, [c], dtype=dtype, is_bias=True)
    mean = helper.create_parameter(
        ParamAttr(name=moving_mean_name, initializer=init_mod.Constant(0.0),
                  trainable=False), [c], dtype=dtype)
    var = helper.create_parameter(
        ParamAttr(name=moving_variance_name, initializer=init_mod.Constant(1.0),
                  trainable=False), [c], dtype=dtype)
    y = helper.create_variable_for_type_inference(input.dtype)
    saved_mean = helper.create_variable_for_type_inference(dtype)
    saved_var = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "batch_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias],
                "Mean": [mean], "Variance": [var]},
        outputs={"Y": [y], "MeanOut": [mean], "VarianceOut": [var],
                 "SavedMean": [saved_mean], "SavedVariance": [saved_var]},
        attrs={"momentum": momentum, "epsilon": epsilon,
               "is_test": is_test or use_global_stats,
               "data_layout": data_layout})
    return helper.append_activation(y, act)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1, epsilon=1e-5,
               param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("layer_norm")
    norm_shape = [int(np.prod(input.shape[begin_norm_axis:]))]
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(param_attr, norm_shape, dtype="float32",
                                    default_initializer=init_mod.Constant(1.0))
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(bias_attr, norm_shape, dtype="float32",
                                    is_bias=True)
        inputs["Bias"] = [b]
    y = helper.create_variable_for_type_inference(input.dtype)
    m = helper.create_variable_for_type_inference("float32")
    v = helper.create_variable_for_type_inference("float32")
    helper.append_op("layer_norm", inputs=inputs,
                     outputs={"Y": [y], "Mean": [m], "Variance": [v]},
                     attrs={"epsilon": epsilon,
                            "begin_norm_axis": begin_norm_axis})
    return helper.append_activation(y, act)


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, name=None):
    helper = LayerHelper("group_norm")
    c = input.shape[1]
    inputs = {"X": [input]}
    if param_attr is not False:
        s = helper.create_parameter(param_attr, [c], dtype="float32",
                                    default_initializer=init_mod.Constant(1.0))
        inputs["Scale"] = [s]
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [c], dtype="float32", is_bias=True)
        inputs["Bias"] = [b]
    y = helper.create_variable_for_type_inference(input.dtype)
    m = helper.create_variable_for_type_inference("float32")
    v = helper.create_variable_for_type_inference("float32")
    helper.append_op("group_norm", inputs=inputs,
                     outputs={"Y": [y], "Mean": [m], "Variance": [v]},
                     attrs={"groups": groups, "epsilon": epsilon})
    return helper.append_activation(y, act)


def instance_norm(input, epsilon=1e-5, param_attr=None, bias_attr=None,
                  name=None):
    helper = LayerHelper("instance_norm")
    c = input.shape[1]
    inputs = {"X": [input]}
    if param_attr is not False:
        s = helper.create_parameter(param_attr, [c], dtype="float32",
                                    default_initializer=init_mod.Constant(1.0))
        inputs["Scale"] = [s]
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [c], dtype="float32", is_bias=True)
        inputs["Bias"] = [b]
    y = helper.create_variable_for_type_inference(input.dtype)
    sm = helper.create_variable_for_type_inference("float32")
    sv = helper.create_variable_for_type_inference("float32")
    helper.append_op("instance_norm", inputs=inputs,
                     outputs={"Y": [y], "SavedMean": [sm], "SavedVariance": [sv]},
                     attrs={"epsilon": epsilon})
    return y


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout")
    out = helper.create_variable_for_type_inference(x.dtype)
    mask = helper.create_variable_for_type_inference("uint8")
    helper.append_op("dropout", inputs={"X": [x]},
                     outputs={"Out": [out], "Mask": [mask]},
                     attrs={"dropout_prob": dropout_prob, "is_test": is_test,
                            "dropout_implementation": dropout_implementation})
    return out


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32"):
    """Reference layers/nn.py embedding → lookup_table op. is_sparse=True
    produces a SelectedRows-equivalent row-sparse gradient (O(batch) HBM
    instead of O(vocab); ops/sparse_grad.py) that the optimizer kernels
    scatter-apply."""
    helper = LayerHelper("embedding")
    w = helper.create_parameter(param_attr, list(size), dtype=dtype)
    if is_distributed:
        w.is_distributed = True
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("lookup_table", inputs={"W": [w], "Ids": [input]},
                     outputs={"Out": [out]},
                     attrs={"padding_idx": -1 if padding_idx is None
                            else padding_idx,
                            "is_sparse": bool(is_sparse)})
    return out


def _unary_layer(op_type):
    def f(x, name=None):
        helper = LayerHelper(op_type)
        out = helper.create_variable_for_type_inference(x.dtype)
        helper.append_op(op_type, inputs={"X": [x]}, outputs={"Out": [out]})
        return out
    f.__name__ = op_type
    return f


relu = _unary_layer("relu")
sigmoid = _unary_layer("sigmoid")
tanh = _unary_layer("tanh")
exp = _unary_layer("exp")
sqrt = _unary_layer("sqrt")
abs = _unary_layer("abs")
square = _unary_layer("square")
log = _unary_layer("log")
floor = _unary_layer("floor")
ceil = _unary_layer("ceil")
round = _unary_layer("round")
sign = _unary_layer("sign")
cos = _unary_layer("cos")
sin = _unary_layer("sin")
softplus = _unary_layer("softplus")
swish = _unary_layer("swish")
hard_swish = _unary_layer("hard_swish")
hard_sigmoid = _unary_layer("hard_sigmoid")
relu6 = _unary_layer("relu6")
logical_not = _unary_layer("logical_not")


def softmax(input, axis=-1, name=None, use_cudnn=False):
    helper = LayerHelper("softmax")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("softmax", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"axis": axis})
    return out


def log_softmax(input, axis=-1, name=None):
    helper = LayerHelper("log_softmax")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("log_softmax", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs={"axis": axis})
    return out


def gelu(x, approximate=False, name=None):
    helper = LayerHelper("gelu")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("gelu", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"approximate": approximate})
    return out


def leaky_relu(x, alpha=0.02, name=None):
    helper = LayerHelper("leaky_relu")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("leaky_relu", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"alpha": alpha})
    return out


def prelu(x, mode="all", param_attr=None, name=None):
    helper = LayerHelper("prelu")
    if mode == "all":
        shape = [1]
    elif mode == "channel":
        shape = [x.shape[1]]
    else:
        shape = [int(np.prod(x.shape[1:]))]
    alpha = helper.create_parameter(param_attr, shape, dtype="float32",
                                    default_initializer=init_mod.Constant(0.25))
    # prelu(x) = max(x, 0) + alpha * min(x, 0) built from primitive ops
    pos = relu(x)
    neg_in = elementwise_sub(x, pos)
    neg = elementwise_mul(neg_in, alpha, axis=1 if mode == "channel" else -1)
    return elementwise_add(pos, neg)


def brelu(x, t_min=0.0, t_max=24.0, name=None):
    helper = LayerHelper("brelu")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("clip", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"min": t_min, "max": t_max})
    return out


def _binary_layer(op_type, out_slot="Out"):
    def f(x, y, axis=-1, act=None, name=None):
        helper = LayerHelper(op_type)
        out = helper.create_variable_for_type_inference(x.dtype)
        helper.append_op(op_type, inputs={"X": [x], "Y": [y]},
                         outputs={out_slot: [out]}, attrs={"axis": axis})
        return helper.append_activation(out, act)
    f.__name__ = op_type
    return f


elementwise_add = _binary_layer("elementwise_add")
elementwise_sub = _binary_layer("elementwise_sub")
elementwise_mul = _binary_layer("elementwise_mul")
elementwise_div = _binary_layer("elementwise_div")
elementwise_max = _binary_layer("elementwise_max")
elementwise_min = _binary_layer("elementwise_min")
elementwise_pow = _binary_layer("elementwise_pow")
elementwise_mod = _binary_layer("elementwise_mod")
elementwise_floordiv = _binary_layer("elementwise_floordiv")


def _compare_layer(op_type):
    def f(x, y, cond=None, name=None):
        helper = LayerHelper(op_type)
        out = cond or helper.create_variable_for_type_inference("bool")
        helper.append_op(op_type, inputs={"X": [x], "Y": [y]},
                         outputs={"Out": [out]})
        return out
    f.__name__ = op_type
    return f


equal = _compare_layer("equal")
not_equal = _compare_layer("not_equal")
less_than = _compare_layer("less_than")
less_equal = _compare_layer("less_equal")
greater_than = _compare_layer("greater_than")
greater_equal = _compare_layer("greater_equal")
logical_and = _compare_layer("logical_and")
logical_or = _compare_layer("logical_or")
logical_xor = _compare_layer("logical_xor")


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("matmul", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]},
                     attrs={"transpose_X": transpose_x,
                            "transpose_Y": transpose_y, "alpha": alpha})
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("mul", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]},
                     attrs={"x_num_col_dims": x_num_col_dims,
                            "y_num_col_dims": y_num_col_dims})
    return out


def _reduce_layer(op_type):
    def f(input, dim=None, keep_dim=False, name=None):
        helper = LayerHelper(op_type)
        out = helper.create_variable_for_type_inference(input.dtype)
        if dim is None:
            attrs = {"reduce_all": True, "dim": [0], "keep_dim": keep_dim}
        else:
            attrs = {"dim": dim if isinstance(dim, (list, tuple)) else [dim],
                     "keep_dim": keep_dim, "reduce_all": False}
        helper.append_op(op_type, inputs={"X": [input]},
                         outputs={"Out": [out]}, attrs=attrs)
        return out
    f.__name__ = op_type
    return f


reduce_sum = _reduce_layer("reduce_sum")
reduce_mean = _reduce_layer("reduce_mean")
reduce_max = _reduce_layer("reduce_max")
reduce_min = _reduce_layer("reduce_min")
reduce_prod = _reduce_layer("reduce_prod")


def mean(x, name=None):
    helper = LayerHelper("mean")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("mean", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    helper = LayerHelper("scale")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("scale", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"scale": scale, "bias": bias,
                            "bias_after_scale": bias_after_scale})
    return helper.append_activation(out, act)


def cast(x, dtype):
    helper = LayerHelper("cast")
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("cast", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"out_dtype": dtype_name(convert_dtype(dtype)),
                            "in_dtype": dtype_name(x.dtype)})
    return out


def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper("reshape2")
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("reshape2", inputs={"X": [x]},
                     outputs={"Out": [out], "XShape": [xshape]},
                     attrs={"shape": list(shape)})
    return helper.append_activation(out, act)


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose2")
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("transpose2", inputs={"X": [x]},
                     outputs={"Out": [out], "XShape": [xshape]},
                     attrs={"axis": list(perm)})
    return out


def concat(input, axis=0, name=None):
    helper = LayerHelper("concat")
    out = helper.create_variable_for_type_inference(input[0].dtype)
    helper.append_op("concat", inputs={"X": list(input)},
                     outputs={"Out": [out]}, attrs={"axis": axis})
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split")
    axis = dim % len(input.shape)
    if isinstance(num_or_sections, int):
        n = num_or_sections
        attrs = {"num": n, "sections": [], "axis": axis}
    else:
        n = len(num_or_sections)
        attrs = {"num": 0, "sections": list(num_or_sections), "axis": axis}
    outs = [helper.create_variable_for_type_inference(input.dtype)
            for _ in range(n)]
    helper.append_op("split", inputs={"X": [input]}, outputs={"Out": outs},
                     attrs=attrs)
    return outs


def stack(x, axis=0, name=None):
    helper = LayerHelper("stack")
    out = helper.create_variable_for_type_inference(x[0].dtype)
    helper.append_op("stack", inputs={"X": list(x)}, outputs={"Y": [out]},
                     attrs={"axis": axis})
    return out


def unstack(x, axis=0, num=None, name=None):
    helper = LayerHelper("unstack")
    n = num if num is not None else x.shape[axis]
    outs = [helper.create_variable_for_type_inference(x.dtype)
            for _ in range(n)]
    helper.append_op("unstack", inputs={"X": [x]}, outputs={"Y": outs},
                     attrs={"axis": axis})
    return outs


def squeeze(input, axes, name=None):
    helper = LayerHelper("squeeze2")
    out = helper.create_variable_for_type_inference(input.dtype)
    xshape = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("squeeze2", inputs={"X": [input]},
                     outputs={"Out": [out], "XShape": [xshape]},
                     attrs={"axes": list(axes)})
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze2")
    out = helper.create_variable_for_type_inference(input.dtype)
    xshape = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("unsqueeze2", inputs={"X": [input]},
                     outputs={"Out": [out], "XShape": [xshape]},
                     attrs={"axes": list(axes)})
    return out


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten2")
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("flatten2", inputs={"X": [x]},
                     outputs={"Out": [out], "XShape": [xshape]},
                     attrs={"axis": axis})
    return out


def slice(input, axes, starts, ends, name=None):
    helper = LayerHelper("slice")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("slice", inputs={"Input": [input]},
                     outputs={"Out": [out]},
                     attrs={"axes": list(axes), "starts": list(starts),
                            "ends": list(ends)})
    return out


def gather(input, index, overwrite=True, axis=0):
    helper = LayerHelper("gather")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("gather", inputs={"X": [input], "Index": [index]},
                     outputs={"Out": [out]}, attrs={"axis": axis})
    return out


def gather_nd(input, index, name=None):
    helper = LayerHelper("gather_nd")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("gather_nd", inputs={"X": [input], "Index": [index]},
                     outputs={"Out": [out]})
    return out


def scatter(input, index, updates, overwrite=True, name=None):
    helper = LayerHelper("scatter")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("scatter",
                     inputs={"X": [input], "Ids": [index], "Updates": [updates]},
                     outputs={"Out": [out]}, attrs={"overwrite": overwrite})
    return out


def expand(x, expand_times, name=None):
    helper = LayerHelper("expand")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("expand", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"expand_times": list(expand_times)})
    return out


def one_hot(input, depth, allow_out_of_range=False):
    helper = LayerHelper("one_hot")
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op("one_hot", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"depth": depth})
    return out


def topk(input, k, name=None):
    helper = LayerHelper("top_k")
    vals = helper.create_variable_for_type_inference(input.dtype)
    idxs = helper.create_variable_for_type_inference("int64")
    helper.append_op("top_k", inputs={"X": [input]},
                     outputs={"Out": [vals], "Indices": [idxs]},
                     attrs={"k": k})
    return vals, idxs


def argmax(x, axis=0, name=None):
    helper = LayerHelper("arg_max")
    out = helper.create_variable_for_type_inference("int64")
    helper.append_op("arg_max", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"axis": axis})
    return out


def argmin(x, axis=0, name=None):
    helper = LayerHelper("arg_min")
    out = helper.create_variable_for_type_inference("int64")
    helper.append_op("arg_min", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"axis": axis})
    return out


def argsort(input, axis=-1, descending=False, name=None):
    helper = LayerHelper("argsort")
    out = helper.create_variable_for_type_inference(input.dtype)
    idxs = helper.create_variable_for_type_inference("int64")
    helper.append_op("argsort", inputs={"X": [input]},
                     outputs={"Out": [out], "Indices": [idxs]},
                     attrs={"axis": axis, "descending": descending})
    return out, idxs


def accuracy(input, label, k=1, correct=None, total=None):
    """Reference layers/metric_op.py accuracy: top_k + accuracy op."""
    helper = LayerHelper("accuracy")
    vals, idxs = topk(input, k)
    acc = helper.create_variable_for_type_inference("float32")
    correct = correct or helper.create_variable_for_type_inference("int32")
    total = total or helper.create_variable_for_type_inference("int32")
    helper.append_op("accuracy",
                     inputs={"Out": [vals], "Indices": [idxs],
                             "Label": [label]},
                     outputs={"Accuracy": [acc], "Correct": [correct],
                              "Total": [total]})
    return acc


def auc(input, label, curve="ROC", num_thresholds=4095, topk=1, slide_steps=1):
    """Reference layers/metric_op.py auc: streaming AUC with persistable stats."""
    helper = LayerHelper("auc")
    stat_pos = helper.create_global_variable([num_thresholds + 1], "int64")
    stat_neg = helper.create_global_variable([num_thresholds + 1], "int64")
    for v in (stat_pos, stat_neg):
        init_mod.Constant(0)(v)
    auc_out = helper.create_variable_for_type_inference("float64")
    helper.append_op("auc",
                     inputs={"Predict": [input], "Label": [label],
                             "StatPos": [stat_pos], "StatNeg": [stat_neg]},
                     outputs={"AUC": [auc_out], "StatPosOut": [stat_pos],
                              "StatNegOut": [stat_neg]},
                     attrs={"num_thresholds": num_thresholds})
    return auc_out, [stat_pos, stat_neg]


def clip(x, min, max, name=None):
    helper = LayerHelper("clip")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("clip", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"min": min, "max": max})
    return out


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper("clip_by_norm")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("clip_by_norm", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"max_norm": max_norm})
    return out


def sums(input, out=None):
    helper = LayerHelper("sum")
    out = out or helper.create_variable_for_type_inference(input[0].dtype)
    helper.append_op("sum", inputs={"X": list(input)}, outputs={"Out": [out]})
    return out


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    sq = square(x)
    ssum = reduce_sum(sq, dim=axis, keep_dim=True)
    norm = sqrt(elementwise_max(ssum, fill_constant_like(ssum, epsilon)))
    return elementwise_div(x, norm)


def fill_constant_like(x, value):
    from .tensor import fill_constant
    return fill_constant(shape=list(x.shape), dtype=dtype_name(x.dtype),
                         value=value)


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper("pad")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("pad", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"paddings": list(paddings), "pad_value": pad_value})
    return out


def pad2d(input, paddings=(0, 0, 0, 0), mode="constant", pad_value=0.0,
          data_format="NCHW", name=None):
    helper = LayerHelper("pad2d")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("pad2d", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"paddings": list(paddings), "mode": mode,
                            "pad_value": pad_value})
    return out


def image_resize(input, out_shape=None, scale=None, resample="BILINEAR",
                 name=None):
    helper = LayerHelper("interpolate")
    out = helper.create_variable_for_type_inference(input.dtype)
    method = {"BILINEAR": "bilinear", "NEAREST": "nearest",
              "BICUBIC": "bicubic"}[resample]
    attrs = {"interp_method": method}
    if out_shape is not None:
        attrs["out_h"], attrs["out_w"] = out_shape
    else:
        attrs["scale"] = scale
    helper.append_op("interpolate", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def resize_nearest(input, out_shape=None, scale=None, name=None):
    return image_resize(input, out_shape, scale, "NEAREST")


def resize_bilinear(input, out_shape=None, scale=None, name=None):
    return image_resize(input, out_shape, scale, "BILINEAR")


def where(condition, x, y, name=None):
    helper = LayerHelper("where")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("where",
                     inputs={"Condition": [condition], "X": [x], "Y": [y]},
                     outputs={"Out": [out]})
    return out


def cond_take(condition, x):
    raise NotImplementedError(
        "dynamic-shape cond_take is eager-only on TPU; use dygraph mode")


def unique(x, dtype="int64"):
    helper = LayerHelper("unique")
    out = helper.create_variable_for_type_inference(x.dtype)
    index = helper.create_variable_for_type_inference(dtype)
    helper.append_op("unique", inputs={"X": [x]},
                     outputs={"Out": [out], "Index": [index]})
    return out, index


def cumsum(x, axis=-1, exclusive=False, reverse=False):
    helper = LayerHelper("cumsum")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("cumsum", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"axis": axis, "exclusive": exclusive,
                            "reverse": reverse})
    return out


def pow(x, factor=1.0, name=None):
    helper = LayerHelper("pow")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("pow", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"factor": factor})
    return out


def fused_attention(q, k, v, mask=None, scale=None, dropout=0.0,
                    causal=False, name=None, sequence_parallel=False,
                    sp_mode="ring", window=None, select=None,
                    return_target=False, layout="bhsd"):
    """Fused multi-head attention on [B, nh, S, hd] tensors (reference
    fused/multihead_matmul_op.cu); pallas flash kernel on TPU. `k` and `v`
    may carry fewer heads, [B, nkv, S, hd] with nkv dividing nh: query head
    h attends KV head h // (nh / nkv). `layout` "bshd": q [B, S, nh, hd],
    k and v [B, S, nkv, hd] and the result [B, S, nh, hd_v], a projection's
    [B, S, heads * hd] under a reshape and no transpose; where the flash
    kernels can index heads as lane blocks of those rows they take them as
    they lie, everywhere else the op transposes inside itself, so a builder
    may always pass it (ops/attention.py, "Layout"). With `causal`, `window` w lets a
    query at position i see keys i-w+1..i only. With
    sequence_parallel=True the op runs ring attention (sp_mode="ring") or
    Ulysses all-to-all (sp_mode="ulysses") over the mesh's sp axis — the
    long-context path the reference lacks (parallel/ring_attention.py).
    `select` [B, S, S] int8 (`sparse_index`'s; with `causal` alone): a query
    attends the keys where it is 1, the same for every head of a row; no
    gradient reaches it. With `return_target` the result is (out, target):
    `target` [B, S, S] float32, the mean over the query heads of the
    probabilities on the selected pairs, no gradient through it."""
    helper = LayerHelper("fused_attention")
    out = helper.create_variable_for_type_inference(q.dtype)
    # the flash kernels' per-row logsumexp, read by the op's grad rule
    # (ops/attention.py); an empty placeholder on every other route
    lse = helper.create_variable_for_type_inference("float32")
    lse.stop_gradient = True
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if mask is not None:
        inputs["Mask"] = [mask]
    attrs = {"dropout": dropout, "causal": causal, "is_test": False,
             "sequence_parallel": bool(sequence_parallel),
             "sp_mode": sp_mode}
    if layout != "bhsd":
        if layout != "bshd":
            raise ValueError(f"fused_attention: unknown layout {layout!r}")
        attrs["layout"] = layout
    if scale is not None:
        attrs["scale"] = scale
    if window is not None:
        if not causal or int(window) < 1:
            raise ValueError(f"fused_attention: window={window} needs "
                             "causal=True and window >= 1")
        attrs["window"] = int(window)
    outputs = {"Out": [out], "Lse": [lse]}
    if select is not None:
        if not causal or window is not None or mask is not None or dropout:
            raise ValueError("fused_attention: select goes with causal=True "
                             "alone (no mask, dropout or window)")
        inputs["Select"] = [select]
    if return_target:
        if select is None:
            raise ValueError("fused_attention: return_target needs select")
        attrs["return_target"] = True
        target = helper.create_variable_for_type_inference("float32")
        target.stop_gradient = True
        outputs["Target"] = [target]
    helper.append_op("fused_attention", inputs=inputs, outputs=outputs,
                     attrs=attrs)
    return (out, target) if return_target else out


def switch_moe(input, num_experts, d_ff, capacity_factor=1.25, name=None,
               top_k=1):
    """Switch-style gated MoE FFN (beyond-reference: makes
    expert_parallel_degree real; ops/moe.py). top_k=1 is Switch routing,
    top_k=2 is GShard (second choice queues behind all first choices, pair
    gates renormalized). Returns (out, aux_loss) — add aux_loss (scaled
    ~0.01) to the training loss for load balancing. Expert weights are
    named '<prefix>_expert_w1/w2' so moe_sharding_rules() can shard their
    leading [E] dim over the mesh's ep axis."""
    helper = LayerHelper(name or "switch_moe")
    d = input.shape[-1]
    from ..framework import unique_name
    prefix = unique_name.generate(name or "switch_moe")
    wg = helper.create_parameter(
        ParamAttr(name=f"{prefix}_gate_w"), [d, num_experts],
        dtype=dtype_name(input.dtype))
    w1 = helper.create_parameter(
        ParamAttr(name=f"{prefix}_expert_w1"), [num_experts, d, d_ff],
        dtype=dtype_name(input.dtype))
    b1 = helper.create_parameter(
        ParamAttr(name=f"{prefix}_expert_b1"), [num_experts, d_ff],
        dtype=dtype_name(input.dtype), is_bias=True)
    w2 = helper.create_parameter(
        ParamAttr(name=f"{prefix}_expert_w2"), [num_experts, d_ff, d],
        dtype=dtype_name(input.dtype))
    b2 = helper.create_parameter(
        ParamAttr(name=f"{prefix}_expert_b2"), [num_experts, d],
        dtype=dtype_name(input.dtype), is_bias=True)
    out = helper.create_variable_for_type_inference(input.dtype)
    aux = helper.create_variable_for_type_inference(input.dtype)
    gidx = helper.create_variable_for_type_inference("int64")
    helper.append_op("switch_moe",
                     inputs={"X": [input], "GateW": [wg],
                             "ExpertW1": [w1], "ExpertB1": [b1],
                             "ExpertW2": [w2], "ExpertB2": [b2]},
                     outputs={"Out": [out], "AuxLoss": [aux],
                              "GateIdx": [gidx]},
                     attrs={"capacity_factor": float(capacity_factor),
                            "top_k": int(top_k)})
    return out, aux


def routed_moe(input, gate_w, expert_gate, expert_up, expert_down, top_k,
               select_bias=None, routed_scaling=1.0, norm_topk=True,
               experts_total=None, expert_offset=0, scoring="sigmoid",
               n_group=1, topk_group=1, expert_input=None,
               norm_topk_eps=None):
    """The routed part of a sparse decoder LM's expert layer (DeepSeek-V3
    family; ops/moe.py routed_moe): scores in float32 over ALL
    `experts_total` experts (`gate_w` [d, experts_total]), `scoring`
    "sigmoid" of each logit or "softmax" over all of them, the top_k of
    scores + `select_bias` (a buffer no gradient reaches), weights = the
    scores at those indices, normalised to sum 1 (`norm_topk`) and times
    `routed_scaling`; the sum they are divided by gets `norm_topk_eps`
    added (None: the op's 1e-20). `n_group` > 1 limits the selection to
    groups: the
    experts in `n_group` equal groups of consecutive ones, a group's score
    the sum of its two highest scores + `select_bias`, the top_k taken
    among the experts of the best `topk_group` groups. No capacity: no
    token is dropped. The caller passes
    the gated experts it HOLDS, `expert_gate/up` [E_held, d, f] and
    `expert_down` [E_held, f, d], experts `expert_offset` .. +E_held of the
    whole; the result is their part of sum_k w_k E_{i_k}(x), so the parts
    of all shares add up to the layer. `expert_gate` None: the experts
    have no gate, E(x) = W_down relu(W_up x)^2. A shared expert is ordinary
    `swiglu` (or `relu2`) ops beside this one. `expert_input` [..., d_e]:
    what the experts read and write where it is not what the router scores
    (a latent of `input`: the experts are [E_held, d_e, f] / [E_held, f,
    d_e] and `out` has its shape); None: `input`. The row buffers hold
    min(top_k, E_held) x N rows: a token cannot pick one expert twice.
    Returns (out, top_idx [N, top_k], expert_load [E_held]: assignments
    that fell on each held expert)."""
    helper = LayerHelper("routed_moe")
    out = helper.create_variable_for_type_inference(
        (input if expert_input is None else expert_input).dtype)
    idx = helper.create_variable_for_type_inference("int64")
    load = helper.create_variable_for_type_inference("int32")
    # what the op's grad rule reads beside idx and load (ops/moe.py): the
    # gate (where the experts have one) and up projections of the sorted
    # rows, the slots' weights in sorted order, the sort and its inverse
    dtypes = {"H": expert_up.dtype, "U": expert_up.dtype,
              "SortedW": "float32", "Order": "int32", "Inv": "int32"}
    if expert_gate is None:
        del dtypes["H"]
    residuals = {slot: helper.create_variable_for_type_inference(dtype)
                 for slot, dtype in dtypes.items()}
    for v in (idx, load) + tuple(residuals.values()):
        v.stop_gradient = True
    inputs = {"X": [input], "GateW": [gate_w]}
    if expert_gate is not None:
        inputs["ExpertGate"] = [expert_gate]
    inputs.update({"ExpertUp": [expert_up], "ExpertDown": [expert_down]})
    if select_bias is not None:
        inputs["SelectBias"] = [select_bias]
    if expert_input is not None:
        inputs["ExpertX"] = [expert_input]
    attrs = {"top_k": int(top_k), "routed_scaling": float(routed_scaling),
             "norm_topk": bool(norm_topk),
             "experts_total": int(experts_total if experts_total is not None
                                  else gate_w.shape[1]),
             "expert_offset": int(expert_offset), "scoring": scoring}
    if n_group > 1:
        attrs.update(n_group=int(n_group), topk_group=int(topk_group))
    if norm_topk_eps is not None:
        attrs["norm_topk_eps"] = float(norm_topk_eps)
    helper.append_op(
        "routed_moe", inputs=inputs,
        outputs={"Out": [out], "TopIdx": [idx], "ExpertLoad": [load],
                 **{slot: [v] for slot, v in residuals.items()}},
        attrs=attrs)
    return out, idx, load


def rms_norm(input, epsilon=1e-6, param_attr=None, name=None):
    """x / sqrt(mean(x^2, last axis) + epsilon) * scale; the scale is a
    parameter of the last axis' size, initialised to 1."""
    helper = LayerHelper("rms_norm")
    scale = helper.create_parameter(param_attr, [int(input.shape[-1])],
                                    dtype="float32",
                                    default_initializer=init_mod.Constant(1.0))
    y = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("rms_norm", inputs={"X": [input], "Scale": [scale]},
                     outputs={"Y": [y]}, attrs={"epsilon": float(epsilon)})
    return y


def rotary_embedding(x, theta=10000.0, rotary_dim=None, layout="interleaved",
                     rope_type="default", factor=1.0, original_max_position=0,
                     beta_fast=32.0, beta_slow=1.0, scale=1.0, positions=None,
                     sections=None, rotary_start=None):
    """Rotary positions on x [..., S, D]: the last `rotary_dim` features
    (default all) turn by position along axis -2; the rest passes through.
    `rotary_start`: the turned features begin there instead (0: the FIRST
    `rotary_dim`, as a `partial_rotary_factor` under the half-split layout
    reads; the pairs are (j, j + rotary_dim/2) inside the turned part).
    `layout`: pairs "interleaved" (2j, 2j+1) or "half" (j, j + rotary_dim/2).
    `rope_type` names the frequency rule: "default", theta^(-2j/rotary_dim),
    or "yarn", which blends it with the same divided by `factor` over a ramp
    set by `original_max_position`, `beta_fast`, `beta_slow`
    (ops/llm_ops.py rotary_frequencies). cos and sin are multiplied by
    `scale` (yarn's attention factor). `positions` [streams, B, S] with
    `sections` (layout "half"; x [B, S, D] or [B, heads, S, D]): several
    position streams, pair j turning by the stream of the section it falls
    in (`sections` their sizes in pairs, e.g. a published `mrope_section`);
    None: the row's own positions, whatever the sections."""
    helper = LayerHelper("rotary_embedding")
    out = helper.create_variable_for_type_inference(x.dtype)
    attrs = {"theta": float(theta),
             "rotary_dim": int(rotary_dim or x.shape[-1]), "layout": layout,
             "rope_type": rope_type, "factor": float(factor),
             "original_max_position": int(original_max_position),
             "beta_fast": float(beta_fast), "beta_slow": float(beta_slow),
             "scale": float(scale)}
    inputs = {"X": [x]}
    if positions is not None:
        if layout != "half" or not sections:
            raise ValueError("rotary_embedding: positions need layout "
                             "\"half\" and sections")
        inputs["Positions"] = [positions]
        attrs["sections"] = [int(n) for n in sections]
    if rotary_start is not None:
        attrs["rotary_start"] = int(rotary_start)
    helper.append_op("rotary_embedding", inputs=inputs,
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def swiglu(gate, up):
    """silu(gate) * up."""
    helper = LayerHelper("swiglu")
    out = helper.create_variable_for_type_inference(gate.dtype)
    helper.append_op("swiglu", inputs={"Gate": [gate], "Up": [up]},
                     outputs={"Out": [out]})
    return out


def detach(x):
    """x with no gradient behind it (ops/sparse_index.py `detach`): inside
    a recomputed segment too, where `stop_gradient` flags are not read."""
    helper = LayerHelper("detach")
    out = helper.create_variable_for_type_inference(x.dtype)
    out.stop_gradient = True
    helper.append_op("detach", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def sparse_index(q, k, w, topk):
    """The indexer of sparse attention (ops/sparse_index.py): from its
    queries `q` [B, H, S, D], its one key head `k` [B, S, D] and the
    per-head weights `w` [B, S, H], (scores, select, pairs): `scores`
    [B, S, S] float32, sum_j w[t, j] relu(q[t, j] . k[s]) on the causal
    pairs; `select` [B, S, S] int8, 1 on the min(t + 1, topk) keys of query t
    with the largest scores (ties to the lower s), what
    `fused_attention(select=...)` takes; `pairs` [1] the mean count of
    selected keys a query. A gradient reaches q, k, w from `scores` alone."""
    helper = LayerHelper("sparse_index")
    scores = helper.create_variable_for_type_inference("float32")
    select = helper.create_variable_for_type_inference("int8")
    pairs = helper.create_variable_for_type_inference("float32")
    select.stop_gradient = pairs.stop_gradient = True
    helper.append_op("sparse_index",
                     inputs={"QI": [q], "KI": [k], "W": [w]},
                     outputs={"Scores": [scores], "Select": [select],
                              "PairsPerQuery": [pairs]},
                     attrs={"topk": int(topk)})
    return scores, select, pairs


def sparse_index_loss(scores, select, target):
    """mean over queries of KL(target_t || softmax over the selected of
    scores_t): the indexer's own loss; a gradient reaches `scores` only."""
    helper = LayerHelper("sparse_index_loss")
    loss = helper.create_variable_for_type_inference("float32")
    helper.append_op("sparse_index_loss",
                     inputs={"Scores": [scores], "Select": [select],
                             "Target": [target]},
                     outputs={"Loss": [loss]})
    return loss


def relu2(x):
    """relu(x)^2."""
    helper = LayerHelper("relu2")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("relu2", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def causal_conv1d(input, kernel_size, param_attr=None, bias_attr=None,
                  activation=None):
    """Depthwise convolution along the sequence of input [B, S, C] that
    sees the current and the `kernel_size - 1` earlier positions
    (ops/ssm.py causal_conv1d): weight [kernel_size, C], a bias [C] unless
    `bias_attr` is False, then `activation` ("silu" or None)."""
    helper = LayerHelper("causal_conv1d")
    c = int(input.shape[-1])
    inputs = {"X": [input],
              "W": [helper.create_parameter(param_attr, [kernel_size, c],
                                            dtype="float32")]}
    if bias_attr is not False:
        inputs["Bias"] = [helper.create_parameter(
            bias_attr, [c], dtype="float32", is_bias=True)]
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("causal_conv1d", inputs=inputs, outputs={"Out": [out]},
                     attrs={"activation": activation or ""})
    return out


def gated_short_conv(input, kernel_size, param_attr=None):
    """The mixer of a gated short-convolution layer between its two
    projections (ops/ssm.py gated_short_conv): input [B, S, 3C] = [B | C |
    u], out[t] = C[t] * sum_j W[j] (B * u)[t - (kernel_size - 1) + j],
    weight [kernel_size, C] a tap a channel, positions before the row's
    start read as zeros; no bias, no activation. Returns [B, S, C]."""
    helper = LayerHelper("gated_short_conv")
    c = int(input.shape[-1]) // 3
    w = helper.create_parameter(param_attr, [kernel_size, c],
                                dtype="float32")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("gated_short_conv", inputs={"X": [input], "W": [w]},
                     outputs={"Out": [out]})
    return out


def ssm_scan(x, b, c, dt, dt_bias, a_log, d, chunk_size):
    """The selective scan of a Mamba-2 layer in its chunked form
    (ops/ssm.py ssm_scan): x [B, S, H, P], b and c [B, S, G, N] (head h
    reads group h // (H / G)), dt [B, S, H] before its softplus, and the
    per-head parameters dt_bias, a_log (A = -exp(a_log)), d [H]. S must be
    a whole number of chunks. Returns y [B, S, H, P]."""
    helper = LayerHelper("ssm_scan")
    y = helper.create_variable_for_type_inference(x.dtype)
    # what the op's grad rule reads: the state each chunk starts from and
    # the per-token rows
    residuals = {slot: helper.create_variable_for_type_inference("float32")
                 for slot in ("States", "DtSoft", "CumA")}
    for v in residuals.values():
        v.stop_gradient = True
    helper.append_op(
        "ssm_scan",
        inputs={"X": [x], "B": [b], "C": [c], "Dt": [dt],
                "DtBias": [dt_bias], "ALog": [a_log], "D": [d]},
        outputs={"Y": [y], **{slot: [v] for slot, v in residuals.items()}},
        attrs={"chunk_size": int(chunk_size)})
    return y


def gated_group_rms_norm(input, gate, groups, epsilon=1e-5, param_attr=None):
    """GroupRMSNorm(input * silu(gate)) * scale: the statistics over each
    of `groups` equal slices of the last axis; the scale is a parameter of
    the last axis' size, initialised to 1."""
    helper = LayerHelper("gated_group_rms_norm")
    scale = helper.create_parameter(param_attr, [int(input.shape[-1])],
                                    dtype="float32",
                                    default_initializer=init_mod.Constant(1.0))
    y = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("gated_group_rms_norm",
                     inputs={"X": [input], "Gate": [gate], "Scale": [scale]},
                     outputs={"Y": [y]},
                     attrs={"groups": int(groups), "epsilon": float(epsilon)})
    return y


def l2_norm(x, scale=1.0, epsilon=1e-6):
    """x / sqrt(sum(x^2, last axis) + epsilon) * scale."""
    helper = LayerHelper("l2_norm")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("l2_norm", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"scale": float(scale),
                            "epsilon": float(epsilon)})
    return out


def head_gate(x, gate):
    """x [..., H, D] times sigmoid(gate [..., H]): one scalar a head; a
    gate of x's own shape is one an element."""
    helper = LayerHelper("head_gate")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("head_gate", inputs={"X": [x], "Gate": [gate]},
                     outputs={"Out": [out]})
    return out


def kda_gate(x, a_log, dt_bias, lower_bound=None):
    """The log decay of a Kimi-delta layer (ops/kda.py kda_gate): x
    [B, S, H * K], a_log [H], dt_bias [H * K] -> g [B, S, H, K] float32.
    With a `lower_bound` the bounded form, lower_bound * sigmoid(exp(a_log)
    * (x + dt_bias)) in (lower_bound, 0); with None the original gate,
    -exp(a_log) * softplus(x + dt_bias), any number <= 0."""
    helper = LayerHelper("kda_gate")
    g = helper.create_variable_for_type_inference("float32")
    attrs = {} if lower_bound is None else {"lower_bound": float(lower_bound)}
    helper.append_op("kda_gate",
                     inputs={"X": [x], "ALog": [a_log], "DtBias": [dt_bias]},
                     outputs={"G": [g]}, attrs=attrs)
    return g


def kda_scan(q, k, v, g, beta, chunk_size, lower_bound=None, beta_scale=1.0):
    """The gated delta rule in its chunked form (ops/kda.py kda_scan): q, k
    [B, S, H, K], v [B, S, H, V], g [B, S, H, K] a channel's log decay
    (`kda_gate`), beta [B, S, H] before its sigmoid. S must be a whole
    number of chunks. `lower_bound`: what g stays above, a token, where the
    gate has a bound (at -5.5 or above a chunk's decayed products are made
    around the running sums at its blocks' starts); None: any g <= 0, and
    they are made so that no factor passes 1. `beta_scale`: beta =
    beta_scale * sigmoid(.), 2 where the model lets `I - beta k k^T` have
    negative eigenvalues. Returns y [B, S, H, V]."""
    helper = LayerHelper("kda_scan")
    y = helper.create_variable_for_type_inference(v.dtype)
    # what the op's grad rule reads: the state each chunk starts from
    states = helper.create_variable_for_type_inference("float32")
    states.stop_gradient = True
    attrs = {"chunk_size": int(chunk_size)}
    if lower_bound is not None:
        attrs["lower_bound"] = float(lower_bound)
    if beta_scale != 1.0:
        attrs["beta_scale"] = float(beta_scale)
    helper.append_op(
        "kda_scan",
        inputs={"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta]},
        outputs={"Y": [y], "States": [states]}, attrs=attrs)
    return y


# ---------------------------------------------------------------------------
# CRF + chunk evaluation (reference layers/nn.py:710 linear_chain_crf,
# :835 crf_decoding, :1038 chunk_eval — wrappers over ops/decode_ops.py and
# ops/tail_ops.py lowerings)
# ---------------------------------------------------------------------------

def linear_chain_crf(input, label, param_attr=None, length=None):
    """input [b, T, C] padded emissions + per-sequence length; creates the
    [C+2, C] transition parameter (rows 0/1 = start/stop weights, the
    reference linear_chain_crf_op.h layout). Returns the negative
    log-likelihood [b, 1] to minimize."""
    helper = LayerHelper("linear_chain_crf")
    c = int(input.shape[-1])
    trans = helper.create_parameter(param_attr, [c + 2, c],
                                    dtype_name(input.dtype))
    nll = helper.create_variable_for_type_inference(input.dtype)
    alpha = helper.create_variable_for_type_inference(input.dtype)
    em_exps = helper.create_variable_for_type_inference(input.dtype)
    tr_exps = helper.create_variable_for_type_inference(input.dtype)
    ins = {"Emission": [input], "Transition": [trans], "Label": [label]}
    if length is not None:
        ins["SeqLen"] = [length]
    helper.append_op("linear_chain_crf", inputs=ins,
                     outputs={"LogLikelihood": [nll], "Alpha": [alpha],
                              "EmissionExps": [em_exps],
                              "TransitionExps": [tr_exps]})
    return nll


def crf_decoding(input, param_attr, label=None, length=None):
    """Viterbi decode against the SHARED transition parameter (pass the
    same ParamAttr/name used in linear_chain_crf). With label given,
    returns the per-token 0/1 correctness mask like the reference."""
    helper = LayerHelper("crf_decoding")
    attr = ParamAttr._to_attr(param_attr)
    block = helper.main_program.global_block()
    if attr and attr.name and block.has_var(attr.name):
        trans = block.var(attr.name)     # share the trained transitions
    else:
        c = int(input.shape[-1])
        trans = helper.create_parameter(attr, [c + 2, c],
                                        dtype_name(input.dtype))
    path = helper.create_variable_for_type_inference("int64")
    ins = {"Emission": [input], "Transition": [trans]}
    if label is not None:
        ins["Label"] = [label]
    if length is not None:
        ins["SeqLen"] = [length]
    helper.append_op("crf_decoding", inputs=ins,
                     outputs={"ViterbiPath": [path]})
    return path


def chunk_eval(input, label, chunk_scheme, num_chunk_types,
               excluded_chunk_types=None, seq_length=None):
    """Chunk-level precision/recall/F1 (IOB and variants). Returns the
    reference's 6-tuple."""
    helper = LayerHelper("chunk_eval")
    outs = {n: helper.create_variable_for_type_inference("float32")
            for n in ("Precision", "Recall", "F1-Score")}
    for n in ("NumInferChunks", "NumLabelChunks", "NumCorrectChunks"):
        outs[n] = helper.create_variable_for_type_inference("int64")
    ins = {"Inference": [input], "Label": [label]}
    if seq_length is not None:
        ins["SeqLength"] = [seq_length]
    helper.append_op("chunk_eval", inputs=ins,
                     outputs={k: [v] for k, v in outs.items()},
                     attrs={"num_chunk_types": int(num_chunk_types),
                            "chunk_scheme": chunk_scheme,
                            "excluded_chunk_types":
                                list(excluded_chunk_types or [])})
    return (outs["Precision"], outs["Recall"], outs["F1-Score"],
            outs["NumInferChunks"], outs["NumLabelChunks"],
            outs["NumCorrectChunks"])


__all__ += ["linear_chain_crf", "crf_decoding", "chunk_eval"]


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None,
             name=None, path_table=None, path_code=None, is_custom=False,
             is_sparse=False):
    """Reference layers/nn.py hsigmoid (hierarchical_sigmoid_op). The
    weight is [num_classes - 1, D] like the reference (a complete binary
    tree over C leaves has C-1 internal nodes). `is_sparse` is accepted
    for signature parity but the update stays dense — row-sparse optimizer
    state has no TPU win at hsigmoid's num_classes scale."""
    from .. import initializer as I
    helper = LayerHelper("hsigmoid")
    d = int(input.shape[-1])
    num_nodes = int(num_classes) - 1 if not is_custom else \
        int(path_table.shape[-1]) + num_classes
    w = helper.create_parameter(param_attr, [num_nodes, d],
                                dtype=dtype_name(input.dtype),
                                default_initializer=I.Xavier())
    ins = {"X": [input], "W": [w], "Label": [label]}
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [num_nodes],
                                    dtype=dtype_name(input.dtype),
                                    is_bias=True)
        ins["Bias"] = [b]
    if is_custom:
        ins["PathTable"] = [path_table]
        ins["PathCode"] = [path_code]
    out = helper.create_variable_for_type_inference(input.dtype)
    pre = helper.create_variable_for_type_inference(input.dtype)
    w_out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("hierarchical_sigmoid", inputs=ins,
                     outputs={"Out": [out], "PreOut": [pre],
                              "W_Out": [w_out]},
                     attrs={"num_classes": int(num_classes)})
    return out


def edit_distance(input, label, normalized=True, ignored_tokens=None,
                  input_length=None, label_length=None):
    """Reference layers/nn.py edit_distance. Padded-dense + lengths;
    returns (distance, sequence_num)."""
    helper = LayerHelper("edit_distance")
    ins = {"Hyps": [input], "Refs": [label]}
    if input_length is not None:
        ins["HypsLength"] = [input_length]
    if label_length is not None:
        ins["RefsLength"] = [label_length]
    out = helper.create_variable_for_type_inference("float32")
    seq = helper.create_variable_for_type_inference("int32")
    helper.append_op("edit_distance", inputs=ins,
                     outputs={"Out": [out], "SequenceNum": [seq]},
                     attrs={"normalized": bool(normalized)})
    return out, seq


def bilinear_tensor_product(x, y, size, act=None, name=None,
                            param_attr=None, bias_attr=None):
    """Reference layers/nn.py bilinear_tensor_product."""
    from .. import initializer as I
    helper = LayerHelper("bilinear_tensor_product")
    w = helper.create_parameter(
        param_attr, [int(size), int(x.shape[-1]), int(y.shape[-1])],
        dtype=dtype_name(x.dtype), default_initializer=I.Xavier())
    ins = {"X": [x], "Y": [y], "Weight": [w]}
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [1, int(size)],
                                    dtype=dtype_name(x.dtype), is_bias=True)
        ins["Bias"] = [b]
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("bilinear_tensor_product", inputs=ins,
                     outputs={"Out": [out]})
    return helper.append_activation(out, act)


def add_position_encoding(input, alpha, beta, name=None):
    """Reference layers/nn.py add_position_encoding."""
    helper = LayerHelper("add_position_encoding")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("add_position_encoding", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"alpha": float(alpha), "beta": float(beta)})
    return out


def cos_sim(X, Y, name=None):
    """Reference layers/nn.py cos_sim (cos_sim_op.cc): row-wise cosine
    similarity -> [B, 1] (the recommender-system book model's scorer)."""
    helper = LayerHelper("cos_sim")
    out = helper.create_variable_for_type_inference(X.dtype)
    xn = helper.create_variable_for_type_inference(X.dtype)
    yn = helper.create_variable_for_type_inference(X.dtype)
    helper.append_op("cos_sim", inputs={"X": [X], "Y": [Y]},
                     outputs={"Out": [out], "XNorm": [xn], "YNorm": [yn]})
    return out
