"""FLOPs the JOB requires to train a bottleneck ResNet on one image
(``"flops": "resnet_train"`` in a configuration file): 3 x forward, two
FLOPs per multiply-accumulate, as in the chip's published peak."""

BLOCKS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


def forward_macs(model):
    """Multiply-accumulates of one forward pass (stride on the 3x3
    convolution, as the program and torchvision place it), convolutions and
    the classifier; batch norm, ReLU and pooling are not counted.  depth 50
    at 224x224 gives 4,089,184,256."""
    blocks = BLOCKS[model["depth"]]
    w, hw = model["width"], model["image_size"] // 2
    macs = hw * hw * w * 7 * 7 * 3                       # conv0, stride 2
    hw //= 2                                             # 3x3/2 max pool
    cin = w
    for si, n in enumerate(blocks):
        cmid, cout = w * 2 ** si, 4 * w * 2 ** si
        for bi in range(n):
            stride = 2 if (bi == 0 and si > 0) else 1
            out = hw // stride
            macs += hw * hw * cin * cmid                 # 1x1 at input size
            macs += out * out * cmid * cmid * 9          # 3x3, strided
            macs += out * out * cmid * cout              # 1x1
            if bi == 0:
                macs += out * out * cin * cout           # projection shortcut
            cin, hw = cout, out
    return macs + cin * model["num_classes"]


def per_unit(model, dims):
    return 3.0 * 2.0 * forward_macs(model)
