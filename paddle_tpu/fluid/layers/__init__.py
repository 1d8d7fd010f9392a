"""fluid.layers namespace (reference: python/paddle/fluid/layers/__init__.py)."""

from paddle_tpu.fluid.layers.io import data  # noqa: F401
from paddle_tpu.fluid.layers.tensor import (  # noqa: F401
    argmax, argmin, assign, cast, concat, fill_constant,
    fill_constant_batch_size_like, ones, shape, sums, zeros, zeros_like)
from paddle_tpu.fluid.layers.nn import (  # noqa: F401
    affine_channel, affine_grid, grid_sampler, image_resize,
    resize_bilinear, resize_nearest, roi_align, roi_pool,
    argsort, multiplex, warpctc, ctc_greedy_decoder, log_loss, rank_loss, margin_rank_loss, bpr_loss, crop, pad2d, pad_constant_like, random_crop, add_position_encoding, similarity_focus, bilinear_tensor_product, row_conv, unstack, sampling_id,
    accuracy, auc, batch_norm, beam_search, beam_search_decode, chunk_eval,
    clip, conv2d, conv2d_transpose,
    cos_sim, crf_decoding, cross_entropy, dropout, embedding, expand, fc,
    fused_linear_cross_entropy, fused_multi_head_attention,
    kv_attention_prefill_paged, kv_attention_decode_paged,
    kv_attention_verify_paged, rms_norm, dense, kda, gdn, ssd, s6, shortconv,
    expert_ffn_held,
    swiglu_ffn, mla, mla_full, router_bias_update,
    token_sample,
    gather, hsigmoid, huber_loss, l2_normalize, label_smooth, layer_norm,
    linear_chain_crf, log, matmul, mean, mul, nce, one_hot, pool2d,
    reduce_max, reduce_mean, reduce_min, reduce_prod, reduce_sum, reshape,
    scale, scaled_dot_product_attention, sigmoid_cross_entropy_with_logits, slice, softmax,
    softmax_with_cross_entropy, split, square_error_cost, squeeze, stack,
    topk, transpose, unsqueeze)
from paddle_tpu.fluid.layers.rnn import (  # noqa: F401
    dynamic_gru, dynamic_lstm, gru_unit, lstm_unit)
from paddle_tpu.fluid.layers.control_flow import (  # noqa: F401
    DynamicRNN, IfElse, StaticRNN, Switch, While, array_length, array_read,
    array_write, create_array, increment)
from paddle_tpu.fluid.layers.sequence import (  # noqa: F401
    edit_distance, sequence_concat, sequence_conv, sequence_enumerate,
    sequence_erase, sequence_expand, sequence_expand_as, sequence_first_step,
    sequence_last_step, sequence_mask, sequence_pad, sequence_pool,
    sequence_reshape, sequence_reverse, sequence_slice, sequence_softmax,
    sequence_unpad)
from paddle_tpu.fluid.layers.ops import (  # noqa: F401
    abs, ceil, cos, elementwise_add, elementwise_div, elementwise_max,
    elementwise_min, elementwise_mod, elementwise_mul, elementwise_pow,
    elementwise_sub, elu, equal, exp, floor, gelu, greater_equal,
    greater_than, hard_sigmoid, leaky_relu, less_equal, less_than,
    logsigmoid, not_equal, pow, reciprocal, relu, relu6, round, rsqrt,
    sigmoid, sin, softplus, softsign, sqrt, square, swish, tanh,
    tanh_shrink, selu, hard_shrink, soft_shrink, softshrink,
    thresholded_relu, brelu, stanh, maxout, flatten, space_to_depth,
    l1_norm)
from paddle_tpu.fluid.layers.parallel import (  # noqa: F401
    Pipeline, switch_moe)
from paddle_tpu.fluid.layers import detection  # noqa: F401
from paddle_tpu.fluid.layers.detection import (  # noqa: F401
    anchor_generator, bipartite_match, box_coder, density_prior_box,
    detection_map, detection_output, generate_proposals, iou_similarity,
    mine_hard_examples, multi_box_head, multiclass_nms,
    polygon_box_transform, prior_box,
    rpn_target_assign, ssd_loss, target_assign, yolov3_loss)

# round-3 API-surface completion: every public name the reference exports
# from fluid.layers resolves (tests/test_layers_api_parity.py)
from paddle_tpu.fluid.layers.nn import (  # noqa: F401
    adaptive_pool2d, adaptive_pool3d, autoincreased_step_counter,
    clip_by_norm, conv3d, conv3d_transpose, data_norm, dice_loss,
    gaussian_random, gaussian_random_batch_size_like,
    get_tensor_from_selected_rows, group_norm, hash, im2sequence,
    image_resize_short, lod_reset, logical_and, logical_not, logical_or,
    logical_xor, lrn, lstm, mean_iou, merge_selected_rows, pad, pool3d,
    prelu, psroi_pool, py_func, roi_perspective_transform, scatter,
    smooth_l1, soft_relu, sum, teacher_student_sigmoid_loss,
    uniform_random_batch_size_like)
from paddle_tpu.fluid.layers.tensor import (  # noqa: F401
    create_global_var, create_parameter, create_tensor, has_inf, has_nan,
    is_empty, isfinite, load, reverse)
from paddle_tpu.fluid.layers.sequence import sequence_scatter  # noqa: F401
from paddle_tpu.fluid.layers.control_flow import (  # noqa: F401
    Print, reorder_lod_tensor_by_rank, tensor_array_to_tensor)
from paddle_tpu.fluid.layers.detection import (  # noqa: F401
    generate_proposal_labels)
from paddle_tpu.fluid.layers.rnn import dynamic_lstmp  # noqa: F401
from paddle_tpu.fluid.layers.io import (  # noqa: F401
    Preprocessor, PyReader, batch, create_py_reader_by_data, double_buffer,
    open_files, py_reader, random_data_generator, read_file, shuffle)
from paddle_tpu.fluid.learning_rate_scheduler import (  # noqa: F401
    append_LARS, exponential_decay, inverse_time_decay, natural_exp_decay,
    noam_decay, piecewise_decay, polynomial_decay)
