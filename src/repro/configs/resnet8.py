"""ResNet-8 — MLPerf Tiny image classification (32x32x3 CIFAR-10, 77,706
parameters after BN folding); the graph is `core/resnet8.py`."""
RESNET8 = dict(
    input_shape=(32, 32, 3), n_classes=10,
    conv_filters=(16, 32, 64), kernel=(3, 3), shortcut_kernel=(1, 1),
    stacks=3, pool=8,
    params=77706, macs_per_image=12501632, weight_bytes=310824,
    source="arXiv:2106.07597; mlcommons/tiny benchmark/training/"
           "image_classification/keras_model.py (resnet_v1_eembc)",
)
