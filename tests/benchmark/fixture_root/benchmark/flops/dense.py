"""FLOPs of one dense layer, forward and backward (no input gradient)."""


def dense_train_flops(features: int, classes: int) -> int:
    return 2 * 2 * features * classes
