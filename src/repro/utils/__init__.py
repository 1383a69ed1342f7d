from repro.utils.pytree import (
    tree_add,
    tree_scale,
    tree_weighted_sum,
    tree_zeros_like,
    tree_l2_norm,
    tree_sq_norm,
    tree_add_noise,
    tree_size,
    tree_flatten_vector,
    tree_unflatten_vector,
)
