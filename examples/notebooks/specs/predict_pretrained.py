CELLS = [
("md", """
# Use a pretrained network for prediction and feature extraction

The reference ships this workflow as
`example/notebooks/predict-with-pretrained-model.ipynb` against its
Inception-BN ImageNet checkpoint: load a `prefix-symbol.json` +
`prefix-%04d.params` pair with `FeedForward.load`, preprocess an image
(center crop + mean subtraction), read off top-5 classes through a
synset file, then turn the classifier into a feature extractor with
`get_internals`.

No pretrained ImageNet weights ship with this repo, so the first cell
*creates* the zoo artifact — a small convnet trained on a synthetic
10-way image task and saved in the exact checkpoint format. Everything
after that point is verbatim the pretrained-model workflow: if you have
a real converted checkpoint (`tools/caffe_converter/`), set `prefix`
and `synset` to it and skip the training cell.
"""),
("code", """
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath("__file__")))))

import numpy as np
import mxnet_tpu as mx
import logging
logging.getLogger().setLevel(logging.INFO)
mx.random.seed(5); np.random.seed(5)
"""),
("code", """
# --- stand-in for the downloadable zoo checkpoint -----------------------
CLASSES = ['red square NW', 'green square NW', 'blue square NW',
           'red square SE', 'green square SE', 'blue square SE',
           'red bar', 'green bar', 'blue bar', 'background']

def render(cls, rng, size=32):
    img = rng.rand(3, size, size).astype(np.float32) * 0.25
    h = size // 2
    if cls < 6:
        ch, corner = cls % 3, cls // 3
        r0 = c0 = 0 if corner == 0 else h
        img[ch, r0:r0+h, c0:c0+h] += 0.7
    elif cls < 9:
        img[cls - 6, h-3:h+3, :] += 0.7
    return img

def make_set(n, rng):
    y = rng.randint(0, len(CLASSES), n).astype(np.float32)
    x = np.stack([render(int(c), rng) for c in y])
    return x, y

def zoo_net(num_classes):
    data = mx.symbol.Variable("data")
    body = data
    for i, nf in enumerate([16, 32]):
        body = mx.symbol.Convolution(data=body, num_filter=nf,
                                     kernel=(3,3), pad=(1,1),
                                     name='conv%d' % i)
        body = mx.symbol.BatchNorm(data=body, name='bn%d' % i)
        body = mx.symbol.Activation(data=body, act_type='relu',
                                    name='relu%d' % i)
        body = mx.symbol.Pooling(data=body, kernel=(2,2), stride=(2,2),
                                 pool_type='max', name='pool%d' % i)
    gp = mx.symbol.Pooling(data=body, kernel=(8,8), pool_type='avg',
                           name='global_pool')
    fc = mx.symbol.FullyConnected(data=mx.symbol.Flatten(gp),
                                  num_hidden=num_classes, name='fc')
    return mx.symbol.SoftmaxOutput(data=fc, name='softmax')

rng = np.random.RandomState(0)
X, y = make_set(1600, rng)
zoo = mx.model.FeedForward(ctx=mx.cpu(), symbol=zoo_net(len(CLASSES)),
                           num_epoch=3, learning_rate=0.1, momentum=0.9,
                           initializer=mx.initializer.Xavier())
zoo.fit(X=mx.io.NDArrayIter(X, y, batch_size=64, shuffle=True))
prefix, num_round = "Inception/Inception-BN-demo", 3
os.makedirs("Inception", exist_ok=True)
zoo.save(prefix, epoch=num_round)
with open("Inception/synset.txt", "w") as f:
    f.write("\\n".join("n%08d %s" % (i, c) for i, c in enumerate(CLASSES)))
print(sorted(os.listdir("Inception")))
# ----------------------------------------------------------------------
"""),
("md", """
## Load the pretrained model

`numpy_batch_size=1` sizes the predictor executor for single-image
calls.
"""),
("code", """
model = mx.model.FeedForward.load(prefix, num_round, ctx=mx.cpu(),
                                  numpy_batch_size=1)
synset = [l.strip().split(' ', 1)[1]
          for l in open('Inception/synset.txt').readlines()]
print(len(synset), 'classes;', synset[:3], '...')
"""),
("md", """
## Preprocess an input image

The zoo contract: center crop to the square, resize to the network
input, subtract the training mean, add the batch axis. The "photo"
here is a rendered class-3 sample padded into a larger rectangle so
the crop actually does something.
"""),
("code", """
import matplotlib
matplotlib.use("Agg")
import matplotlib.pyplot as plt
%matplotlib inline

true_cls = 3
photo = np.zeros((3, 48, 64), np.float32) + 0.1
photo[:, 8:40, 16:48] = render(true_cls, np.random.RandomState(7))

def PreprocessImage(img, show_img=False):
    # crop the center square
    c, hh, ww = img.shape
    short_edge = min(hh, ww)
    yy, xx = (hh - short_edge) // 2, (ww - short_edge) // 2
    crop = img[:, yy:yy+short_edge, xx:xx+short_edge]
    # resize to the network input (nearest-neighbour keeps numpy-only)
    idx = (np.arange(32) * short_edge // 32)
    resized = crop[:, idx][:, :, idx]
    if show_img:
        plt.imshow(np.clip(resized.transpose(1,2,0), 0, 1)); plt.show()
    # normalize like training (the zoo stand-in trained on raw [0,1.x))
    return resized[np.newaxis].astype(np.float32)

batch = PreprocessImage(photo, show_img=True)
print('input blob:', batch.shape)
"""),
("md", """
## Predict: top-5 through the synset
"""),
("code", """
prob = model.predict(batch)[0]
pred = np.argsort(prob)[::-1]
top1 = pred[0]
print('Top1:', synset[top1], '(p=%.3f)' % prob[top1])
top5 = [synset[p] for p in pred[0:5]]
print('Top5:', top5)
assert top1 == true_cls, (top1, true_cls)
"""),
("md", """
## Extract an internal feature layer

`get_internals` + shared `arg_params` re-binds the trained weights
under a truncated symbol — the pretrained body becomes an embedding
function (the transfer-learning workhorse).
"""),
("code", """
internals = model.symbol.get_internals()
fea_symbol = internals["global_pool_output"]
feature_extractor = mx.model.FeedForward(
    ctx=mx.cpu(), symbol=fea_symbol, numpy_batch_size=1,
    arg_params=model.arg_params, aux_params=model.aux_params,
    allow_extra_params=True)
global_pooling_feature = feature_extractor.predict(batch)
print('feature:', global_pooling_feature.shape)
assert global_pooling_feature.shape == (1, 32, 1, 1)

import shutil; shutil.rmtree("Inception")
"""),
]
