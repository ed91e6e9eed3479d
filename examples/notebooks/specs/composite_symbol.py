CELLS = [
("md", """
# Composing symbols into components

The reference ships this walkthrough as
`example/notebooks/composite_symbol.ipynb`: a `Symbol` is an ordinary
python value, so network *components* are ordinary python functions that
take symbols and return symbols. This notebook builds the Inception-BN
factories and composes the full GoogLeNet-BN body out of them, then
inspects it with shape inference and the visualization helpers.
"""),
("code", """
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath("__file__")))))

import mxnet_tpu as mx
"""),
("code", """
# Basic Conv + BN + ReLU factory
def ConvFactory(data, num_filter, kernel, stride=(1,1), pad=(0, 0),
                name=None, suffix=''):
    conv = mx.symbol.Convolution(data=data, num_filter=num_filter,
                                 kernel=kernel, stride=stride, pad=pad,
                                 name='conv_%s%s' % (name, suffix))
    bn = mx.symbol.BatchNorm(data=conv, name='bn_%s%s' % (name, suffix))
    act = mx.symbol.Activation(data=bn, act_type='relu',
                               name='relu_%s%s' % (name, suffix))
    return act
"""),
("code", """
# A component is just a call: visualize one Conv+BN+ReLU block.
# (No `dot` binary in this image, so we show the DOT source and the
# layer summary instead of rendered SVG — same graph either way.)
prev = mx.symbol.Variable(name="Previous_Output")
conv_comp = ConvFactory(data=prev, num_filter=64, kernel=(7,7), stride=(2,2))
dot = mx.viz.plot_network(symbol=conv_comp)
print(dot.source[:400], '...')
"""),
("code", """
# param mapping to the paper:
# num_1x1      >>>  #1x1
# num_3x3red   >>>  #3x3 reduce
# num_3x3      >>>  #3x3
# num_d3x3red  >>>  double #3x3 reduce
# num_d3x3     >>>  double #3x3
# pool         >>>  pool type
# proj         >>>  pool-path projection filters
def InceptionFactoryA(data, num_1x1, num_3x3red, num_3x3, num_d3x3red,
                      num_d3x3, pool, proj, name):
    # 1x1 tower
    c1x1 = ConvFactory(data=data, num_filter=num_1x1, kernel=(1,1),
                       name=('%s_1x1' % name))
    # 3x3 tower: 1x1 reduce then 3x3
    c3x3r = ConvFactory(data=data, num_filter=num_3x3red, kernel=(1,1),
                        name=('%s_3x3' % name), suffix='_reduce')
    c3x3 = ConvFactory(data=c3x3r, num_filter=num_3x3, kernel=(3,3),
                       pad=(1,1), name=('%s_3x3' % name))
    # double 3x3 tower
    cd3x3r = ConvFactory(data=data, num_filter=num_d3x3red, kernel=(1,1),
                         name=('%s_double_3x3' % name), suffix='_reduce')
    cd3x3 = ConvFactory(data=cd3x3r, num_filter=num_d3x3, kernel=(3,3),
                        pad=(1,1), name=('%s_double_3x3_0' % name))
    cd3x3 = ConvFactory(data=cd3x3, num_filter=num_d3x3, kernel=(3,3),
                        pad=(1,1), name=('%s_double_3x3_1' % name))
    # pool tower + projection
    pooling = mx.symbol.Pooling(data=data, kernel=(3,3), stride=(1,1),
                                pad=(1,1), pool_type=pool,
                                name=('%s_pool_%s_pool' % (pool, name)))
    cproj = ConvFactory(data=pooling, num_filter=proj, kernel=(1,1),
                        name=('%s_proj' % name))
    # concat across channels
    return mx.symbol.Concat(c1x1, c3x3, cd3x3, cproj,
                            name='ch_concat_%s_chconcat' % name)

def InceptionFactoryB(data, num_3x3red, num_3x3, num_d3x3red, num_d3x3,
                      name):
    # the stride-2 (downsampling) block: no 1x1 tower, max-pool path
    c3x3r = ConvFactory(data=data, num_filter=num_3x3red, kernel=(1,1),
                        name=('%s_3x3' % name), suffix='_reduce')
    c3x3 = ConvFactory(data=c3x3r, num_filter=num_3x3, kernel=(3,3),
                       pad=(1,1), stride=(2,2), name=('%s_3x3' % name))
    cd3x3r = ConvFactory(data=data, num_filter=num_d3x3red, kernel=(1,1),
                         name=('%s_double_3x3' % name), suffix='_reduce')
    cd3x3 = ConvFactory(data=cd3x3r, num_filter=num_d3x3, kernel=(3,3),
                        pad=(1,1), name=('%s_double_3x3_0' % name))
    cd3x3 = ConvFactory(data=cd3x3, num_filter=num_d3x3, kernel=(3,3),
                        pad=(1,1), stride=(2,2),
                        name=('%s_double_3x3_1' % name))
    pooling = mx.symbol.Pooling(data=data, kernel=(3,3), stride=(2,2),
                                pad=(1,1), pool_type="max",
                                name=('max_pool_%s_pool' % name))
    return mx.symbol.Concat(c3x3, cd3x3, pooling,
                            name='ch_concat_%s_chconcat' % name)
"""),
("md", """
## Shape arithmetic for one block

With an input shape, `infer_shape` resolves every tower: A-blocks keep
the spatial size and concatenate channels; B-blocks halve the spatial
size.
"""),
("code", """
prev = mx.symbol.Variable(name="Previous_Output")
in3a = InceptionFactoryA(prev, 64, 64, 64, 64, 96, "avg", 32, name='in3a')
_, out_shapes, _ = in3a.infer_shape(Previous_Output=(128, 192, 28, 28))
print('in3a output:', out_shapes[0])
assert out_shapes[0] == (128, 64 + 64 + 96 + 32, 28, 28)  # towers' channels concat

in3c = InceptionFactoryB(prev, 128, 160, 64, 96, name='in3c')
_, out_shapes, _ = in3c.infer_shape(Previous_Output=(128, 256, 28, 28))
print('in3c output:', out_shapes[0])
assert out_shapes[0][2:] == (14, 14)   # stride-2 block halves H, W
"""),
("md", """
## The full Inception-BN body

Stack the factories exactly as the paper does — stage 1-2 stem, three
A/B stages, global average pool, linear classifier.
"""),
("code", """
def inception_bn(num_classes=1000):
    data = mx.symbol.Variable(name="data")
    # stage 1
    conv1 = ConvFactory(data=data, num_filter=64, kernel=(7, 7),
                        stride=(2, 2), pad=(3, 3), name='1')
    pool1 = mx.symbol.Pooling(data=conv1, kernel=(3, 3), stride=(2, 2),
                              name='pool_1', pool_type='max')
    # stage 2
    conv2red = ConvFactory(data=pool1, num_filter=64, kernel=(1, 1),
                           stride=(1, 1), name='2_red')
    conv2 = ConvFactory(data=conv2red, num_filter=192, kernel=(3, 3),
                        stride=(1, 1), pad=(1, 1), name='2')
    pool2 = mx.symbol.Pooling(data=conv2, kernel=(3, 3), stride=(2, 2),
                              name='pool_2', pool_type='max')
    # stage 3
    in3a = InceptionFactoryA(pool2, 64, 64, 64, 64, 96, "avg", 32, '3a')
    in3b = InceptionFactoryA(in3a, 64, 64, 96, 64, 96, "avg", 64, '3b')
    in3c = InceptionFactoryB(in3b, 128, 160, 64, 96, '3c')
    # stage 4
    in4a = InceptionFactoryA(in3c, 224, 64, 96, 96, 128, "avg", 128, '4a')
    in4b = InceptionFactoryA(in4a, 192, 96, 128, 96, 128, "avg", 128, '4b')
    in4c = InceptionFactoryA(in4b, 160, 128, 160, 128, 160, "avg", 128, '4c')
    in4d = InceptionFactoryA(in4c, 96, 128, 192, 160, 192, "avg", 128, '4d')
    in4e = InceptionFactoryB(in4d, 128, 192, 192, 256, '4e')
    # stage 5
    in5a = InceptionFactoryA(in4e, 352, 192, 320, 160, 224, "avg", 128, '5a')
    in5b = InceptionFactoryA(in5a, 352, 192, 320, 192, 224, "max", 128, '5b')
    # global pool + classifier
    avg = mx.symbol.Pooling(data=in5b, kernel=(7, 7), stride=(1, 1),
                            name="global_pool", pool_type='avg')
    flatten = mx.symbol.Flatten(data=avg, name='flatten')
    fc1 = mx.symbol.FullyConnected(data=flatten, num_hidden=num_classes,
                                   name='fc1')
    return mx.symbol.SoftmaxOutput(data=fc1, name='softmax')

softmax = inception_bn()
"""),
("code", """
# End-to-end shape check at the ImageNet input size, and the parameter
# census: every tower the factories created is accounted for.
arg_shapes, out_shapes, aux_shapes = softmax.infer_shape(
    data=(32, 3, 224, 224), softmax_label=(32,))
print('output:', out_shapes[0])
print('arguments: %d   aux states: %d' % (len(arg_shapes), len(aux_shapes)))
n_params = sum(int(__import__('numpy').prod(s)) for s in arg_shapes[1:-1])
print('parameters: %.1fM' % (n_params / 1e6))
assert out_shapes[0] == (32, 1000)
assert len(aux_shapes) == 2 * sum(1 for n in softmax.list_arguments()
                                  if n.endswith('_gamma'))
"""),
("code", """
# The layer summary prints the same composition bottom-up.
mx.viz.print_summary(softmax, shape={"data": (1, 3, 224, 224),
                                     "softmax_label": (1,)},
                     line_length=98)
"""),
("md", """
A component library (the model zoo in `mxnet_tpu/models/`) is nothing
more than these factory functions packaged — `get_resnet`,
`lstm_unroll`, the SSD and RCNN bodies are all built this way.
"""),
]
