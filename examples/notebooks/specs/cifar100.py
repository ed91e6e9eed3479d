CELLS = [
("md", """
# CIFAR-100: train, checkpoint every epoch, resume and finetune

The reference ships this workflow as
`example/notebooks/cifar-100.ipynb`: the Inception body from
`composite_symbol.ipynb` trained on 100-way labels with an epoch-end
checkpoint callback, then — the part the notebook exists to show —
**training continued from a saved epoch** by loading the checkpoint
into a fresh `FeedForward` with `begin_epoch`, optionally at a lower
learning rate (the finetune step).

Budget scaling for the CPU notebook: a 16-way synthetic task and the
small inception body stand in for the 100-class dataset and the full
network — the checkpoint/resume mechanics are identical (swap in
`ImageRecordIter` over the real `.rec` files and `inception(100)` to
reproduce the reference run).
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
mx.random.seed(3); np.random.seed(3)
"""),
("code", """
def ConvFactory(data, num_filter, kernel, stride=(1,1), pad=(0,0),
                name=None, suffix=''):
    conv = mx.symbol.Convolution(data=data, num_filter=num_filter,
                                 kernel=kernel, stride=stride, pad=pad,
                                 name='conv_%s%s' % (name, suffix))
    bn = mx.symbol.BatchNorm(data=conv, name='bn_%s%s' % (name, suffix))
    return mx.symbol.Activation(data=bn, act_type='relu',
                                name='relu_%s%s' % (name, suffix))

def SimpleFactory(data, ch_1x1, ch_3x3, name):
    conv1x1 = ConvFactory(data, ch_1x1, (1,1), name=name+'_1x1')
    conv3x3 = ConvFactory(data, ch_3x3, (3,3), pad=(1,1), name=name+'_3x3')
    return mx.symbol.Concat(conv1x1, conv3x3)

def inception(num_classes):
    data = mx.symbol.Variable(name="data")
    conv1 = ConvFactory(data, 24, (3,3), pad=(1,1), name='1')
    in3a = SimpleFactory(conv1, 8, 12, 'in3a')
    pool3 = mx.symbol.Pooling(data=in3a, kernel=(2,2), stride=(2,2),
                              pool_type='max', name='pool3')
    in4a = SimpleFactory(pool3, 16, 24, 'in4a')
    pool = mx.symbol.Pooling(data=in4a, pool_type="avg", kernel=(8,8),
                             name="global_pool")
    flatten = mx.symbol.Flatten(data=pool, name="flatten1")
    fc = mx.symbol.FullyConnected(data=flatten, num_hidden=num_classes,
                                  name="fc1")
    return mx.symbol.SoftmaxOutput(data=fc, name="softmax")

num_classes = 16
softmax = inception(num_classes)
"""),
("code", """
# synthetic 16-way task: class = (channel, quadrant, coarse intensity)
def make_batchset(n, rng):
    x = rng.rand(n, 3, 16, 16).astype(np.float32) * 0.25
    y = rng.randint(0, num_classes, n).astype(np.float32)
    for i in range(n):
        cls = int(y[i])
        ch, q, lvl = cls % 3, cls % 4, cls // 8
        r0, c0 = (q // 2) * 8, (q % 2) * 8
        x[i, ch, r0:r0+8, c0:c0+8] += 0.45 + 0.35 * lvl
    return x, y

rng = np.random.RandomState(1)
X_train, y_train = make_batchset(1600, rng)
X_val, y_val = make_batchset(800, rng)
batch_size = 64
train_iter = mx.io.NDArrayIter(X_train, y_train, batch_size=batch_size,
                               shuffle=True)
val_iter = mx.io.NDArrayIter(X_val, y_val, batch_size=batch_size)
"""),
("md", """
## Train with an epoch-end checkpoint

`mx.callback.do_checkpoint(prefix)` saves `prefix-symbol.json` once and
`prefix-%04d.params` after every epoch — the same two-file format every
binding reads.
"""),
("code", """
num_epoch = 3
model_prefix = "cifar_100_nb"
model = mx.model.FeedForward(ctx=mx.cpu(), symbol=softmax,
                             num_epoch=num_epoch,
                             learning_rate=0.1, momentum=0.9, wd=0.0001,
                             initializer=mx.initializer.Xavier())
model.fit(X=train_iter, eval_data=val_iter, eval_metric="accuracy",
          epoch_end_callback=mx.callback.do_checkpoint(model_prefix))
acc_before = model.score(val_iter)
print('accuracy after %d epochs: %.3f' % (num_epoch, acc_before))
print(sorted(f for f in os.listdir('.') if f.startswith(model_prefix)))
"""),
("md", """
## Resume from a saved epoch

`FeedForward.load(prefix, epoch)` restores symbol + params;
constructing a new estimator from those arrays with
`begin_epoch=epoch` continues the run — here as a finetune at a tenth
of the learning rate, exactly the reference's recipe for its final
epochs.
"""),
("code", """
# load params from the saved checkpoint
tmp_model = mx.model.FeedForward.load(model_prefix, num_epoch,
                                      ctx=mx.cpu())
# the restored estimator scores identically to the in-memory one
acc_loaded = tmp_model.score(val_iter)
assert abs(acc_loaded - acc_before) < 1e-6, (acc_loaded, acc_before)

# create a new model seeded with those params and train 2 more epochs
finetune_epoch = num_epoch + 2
model2 = mx.model.FeedForward(ctx=mx.cpu(), symbol=softmax,
                              num_epoch=finetune_epoch,
                              arg_params=tmp_model.arg_params,
                              aux_params=tmp_model.aux_params,
                              begin_epoch=num_epoch,
                              learning_rate=0.01, momentum=0.9, wd=0.0001)
model2.fit(X=train_iter, eval_data=val_iter, eval_metric="accuracy",
           epoch_end_callback=mx.callback.do_checkpoint(model_prefix))
"""),
("code", """
acc_after = model2.score(val_iter)
print('accuracy: %.3f after resume+finetune (was %.3f)' % (
    acc_after, acc_before))
# the finetune started FROM the checkpoint (not from scratch): it must
# at least hold the pre-resume accuracy, and the epoch files exist
assert acc_after >= acc_before - 0.02, (acc_after, acc_before)
assert acc_after > 0.85, acc_after
ckpts = sorted(f for f in os.listdir('.') if f.startswith(model_prefix))
print(ckpts)
assert '%s-%04d.params' % (model_prefix, finetune_epoch) in ckpts
for f in ckpts:
    os.remove(f)
"""),
("md", """
Optimizer state is not checkpointed (reference semantics,
`model.py save_checkpoint` — momentum restarts at zero on resume);
for long runs that matters less than the learning-rate schedule, which
`begin_epoch` keeps aligned with `lr_scheduler` epoch counting.
"""),
]
