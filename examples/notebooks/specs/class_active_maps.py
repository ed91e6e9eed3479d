CELLS = [
("md", """
# Class activation maps

The reference ships this workflow as
`example/notebooks/class_active_maps.ipynb` (Zhou et al. 2016,
"Learning Deep Features for Discriminative Localization"): in a network
that ends `conv -> global average pool -> fully connected -> softmax`,
the class score is a *linear* function of the last conv layer's spatial
feature map, so projecting the FC weight row for a class back onto that
feature map yields a heat map of *where* the evidence for the class
lives — localization for free, with no box supervision.

The reference demos it on Inception-v3; here the same mechanics run on
a small convnet trained to classify which channel a bright blob is
drawn in, at a RANDOM position — so the CAM has something real to
localize, and the notebook can assert it points at the blob.
"""),
("code", """
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath("__file__")))))

import numpy as np
import mxnet_tpu as mx
import matplotlib
matplotlib.use("Agg")
import matplotlib.pyplot as plt
%matplotlib inline
mx.random.seed(9); np.random.seed(9)
"""),
("code", """
# blob-location dataset: class = blob's channel; position is uniform
SIZE, BLOB = 24, 7
def make_set(n, rng):
    x = rng.rand(n, 3, SIZE, SIZE).astype(np.float32) * 0.3
    y = rng.randint(0, 3, n).astype(np.float32)
    pos = rng.randint(0, SIZE - BLOB, (n, 2))
    for i in range(n):
        r, c = pos[i]
        x[i, int(y[i]), r:r+BLOB, c:c+BLOB] += 0.8
    return x, y, pos

rng = np.random.RandomState(2)
X_train, y_train, _ = make_set(1600, rng)
X_test, y_test, pos_test = make_set(64, rng)
"""),
("md", """
## A CAM-compatible network

The crucial property: spatial resolution survives until the global
average pool — the convs keep `SIZE x SIZE`, and only `global_pool`
collapses space. `prob_layer` and `conv_layer` name the two outputs the
CAM needs.
"""),
("code", """
data = mx.symbol.Variable("data")
body = data
for i, nf in enumerate([16, 32]):
    body = mx.symbol.Convolution(data=body, num_filter=nf, kernel=(3,3),
                                 pad=(1,1), name='conv%d' % i)
    body = mx.symbol.BatchNorm(data=body, name='bn%d' % i)
    body = mx.symbol.Activation(data=body, act_type='relu',
                                name='relu%d' % i)
gp = mx.symbol.Pooling(data=body, kernel=(SIZE, SIZE), pool_type='avg',
                       name='global_pool')
fc = mx.symbol.FullyConnected(data=mx.symbol.Flatten(gp), num_hidden=3,
                              no_bias=True, name='fc_cam')
softmax = mx.symbol.SoftmaxOutput(data=fc, name='softmax')

model = mx.model.FeedForward(ctx=mx.cpu(), symbol=softmax, num_epoch=3,
                             learning_rate=0.1, momentum=0.9,
                             initializer=mx.initializer.Xavier())
model.fit(X=mx.io.NDArrayIter(X_train, y_train, batch_size=64,
                              shuffle=True))
acc = model.score(mx.io.NDArrayIter(X_test, y_test, batch_size=64))
print('test accuracy: %.3f' % acc)
assert acc > 0.9, acc
"""),
("md", """
## Group the prob and conv outputs

`get_internals` + `Group` gives one executor that returns both the
softmax probabilities and the pre-pool feature map in a single forward
(ref notebook: `mx.sym.Group([internals[prob_layer],
internals[conv_layer]])`).
"""),
("code", """
prob_layer, conv_layer, arg_fc = 'softmax_output', 'relu1_output', 'fc_cam'
internals = softmax.get_internals()
group = mx.symbol.Group([internals[prob_layer], internals[conv_layer]])

mod = mx.model.FeedForward(ctx=mx.cpu(), symbol=group, numpy_batch_size=64,
                           arg_params=model.arg_params,
                           aux_params=model.aux_params,
                           allow_extra_params=True)
outputs = mod.predict(X_test)
score, conv_fm = outputs[0], outputs[1]
weight_fc = model.arg_params[arg_fc + '_weight'].asnumpy()
print('prob:', score.shape, ' conv feature map:', conv_fm.shape,
      ' fc weight:', weight_fc.shape)
"""),
("code", """
def get_cam(conv_feat_map, weight_fc):
    # CAM_k = sum_c w[k, c] * F[c, :, :]  — the FC row projected onto space
    assert len(weight_fc.shape) == 2
    C, H, W = conv_feat_map.shape
    assert weight_fc.shape[1] == C
    cam = weight_fc.dot(conv_feat_map.reshape(C, H * W))
    return cam.reshape(-1, H, W)
"""),
("md", """
## Visualize and verify

Top row: input images. Bottom row: the predicted class's activation
map. The bright region must sit on the blob — asserted below by
checking the CAM's argmax falls inside the (known) blob box for nearly
every test image.
"""),
("code", """
hits = 0
for i in range(len(X_test)):
    cam = get_cam(conv_fm[i], weight_fc)[int(score[i].argmax())]
    r, c = np.unravel_index(cam.argmax(), cam.shape)
    r0, c0 = pos_test[i]
    if r0 - 1 <= r <= r0 + BLOB and c0 - 1 <= c <= c0 + BLOB:
        hits += 1
print('CAM argmax inside the blob box: %d/%d' % (hits, len(X_test)))
assert hits >= 0.85 * len(X_test), hits

plt.figure(figsize=(12, 4))
for k in range(4):
    cam = get_cam(conv_fm[k], weight_fc)[int(score[k].argmax())]
    plt.subplot(2, 4, k + 1)
    plt.imshow(np.clip(X_test[k].transpose(1, 2, 0), 0, 1))
    plt.axis('off'); plt.title('class %d' % int(score[k].argmax()))
    plt.subplot(2, 4, 4 + k + 1)
    plt.imshow(cam, cmap='jet'); plt.axis('off')
plt.tight_layout(); plt.show()
"""),
("md", """
The heat maps track the blob wherever it moves — the FC weights learned
*which feature channels* carry each class, and the conv map says
*where* those features fired. On a real checkpoint the identical code
localizes objects in photographs (the reference's barbell example).
"""),
]
