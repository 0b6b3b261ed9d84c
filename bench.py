"""Benchmark legs and their runner.

Headline (BASELINE.json north star): ResNet50 training throughput,
images/sec/chip, vs a hand-written JAX/Flax ResNet50 train step run in
the same process on the same chip (``vs_baseline`` = ours/flax; 1.0 =
parity with idiomatic flax, the reference implementation the target is
defined against).

The FULL BASELINE.md config list also runs (LeNet/MNIST train,
GravesLSTM char-RNN train vs a hand-written flax/optax ``nn.scan``
baseline, Keras-imported VGG16 inference vs hand-written flax VGG16)
plus the serving / training-infrastructure legs. MFU is reported for
the matmul/conv-dominated configs (model FLOPs / wall-clock / bf16
peak of the detected chip, ``observability/step_profile.py``'s table).

``python bench.py`` runs every leg, each in its own child process
(``--leg NAME``), one after another — the chip belongs to one process
at a time and the runner itself never touches jax. It prints one JSON
line per finished leg on stdout and exits non-zero when a leg fails or
when the devices are not a TPU: a number is printed only by the run
that measured it, and every line names the platform and device kind it
ran on. ``python bench.py --leg NAME`` runs one leg in this process on
whatever backend jax gives it (tests call legs in-process on the CPU;
their lines say ``"platform": "cpu"``). ``--headline-only`` (or env
BENCH_HEADLINE_ONLY=1) stops after the ResNet50 f32 leg.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

BATCH = 128
IMG = 224
STEPS = 40
WARMUP = 5
LENET_BATCH = 128
LENET_STEPS = 600


def _device():
    """(platform, device_kind, bf16 peak FLOP/s) of the first device.
    A kind the peak table does not know is an error, not an omitted
    MFU; the CPU (in-process test calls) has no peak."""
    import jax

    from deeplearning4j_tpu.observability.step_profile import (
        peak_flops_for_kind)
    dev = jax.devices()[0]
    peak = peak_flops_for_kind(dev.device_kind)
    if peak is None and dev.platform != "cpu":
        raise RuntimeError(
            f"no bf16 peak known for device kind {dev.device_kind!r} "
            "(observability/step_profile.py's table)")
    return dev.platform, dev.device_kind, peak


def _make_measure(step_fn, args, steps, warmup, get_loss):
    """Compile + warm up now; return a zero-arg measure() giving the
    wall time of one ``steps``-burst, ended by FETCHING a value that
    depends on every step (dispatch is asynchronous: without the
    fetch the clock measures the enqueue). Noise is additive-positive
    (host contention, drift), so the caller takes the MIN of N
    interleaved bursts — the robust estimator. (Two-point subtraction
    of burst pairs was tried and rejected: subtracting makes the noise
    signed, and under heavy drift the difference can even go
    negative.)"""
    import jax.numpy as jnp
    for _ in range(warmup):
        args = step_fn(*args)
    float(jnp.sum(get_loss(args)))
    holder = {"args": args}

    def measure() -> float:
        a = holder["args"]
        t0 = time.perf_counter()
        for _ in range(steps):
            a = step_fn(*a)
        float(jnp.sum(get_loss(a)))     # the end-of-burst fetch
        holder["args"] = a
        return time.perf_counter() - t0

    return measure


def _interleave(measure_ours, measure_ref, repeats=3):
    """Best-of-N with alternating bursts: (ours_dt, ref_dt)."""
    best_o = best_r = float("inf")
    for _ in range(max(1, repeats)):
        best_o = min(best_o, measure_ours())
        best_r = min(best_r, measure_ref())
    return best_o, best_r


def _time_infer(fn, x, steps, warmup):
    """Inference timing with a data dependency chaining step N+1 on
    step N's output, so the final fetch depends on every step. Large
    single bursts + caller min-of-N (see _make_measure's noise note).
    ``chained`` is deliberately NOT jitted: fn may close over big
    weights, and a jit here would bake them into the HLO as constants
    (see bench_flax_vgg16_infer); the tiny select runs as a second
    dispatch instead."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _link(out, x):
        # the next input depends on this step's output (isnan is
        # runtime-only, uncomputable at compile) and drifts: +1e-4 per
        # step is irrelevant to N(0,1) image stats
        bump = jnp.where(jnp.isnan(jnp.mean(out)),
                         jnp.asarray(2e-4, x.dtype),
                         jnp.asarray(1e-4, x.dtype))
        return x + bump

    def chained(x):
        out = fn(x)
        return _link(out, x), out

    xx = jnp.asarray(x)
    for _ in range(warmup):
        xx, out = chained(xx)
    float(jnp.sum(out))

    t0 = time.perf_counter()
    a = xx
    for _ in range(steps):
        a, out = chained(a)
    float(jnp.sum(out))                 # the end-of-burst fetch
    return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# 1. ResNet50 training (headline)
# ---------------------------------------------------------------------------

def bench_ours(batch=BATCH, img=IMG, steps=STEPS, prep=False):
    import jax
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.zoo import ResNet50

    net = ResNet50(n_classes=1000, input_shape=(img, img, 3),
                   updater=updaters.nesterovs(0.1, 0.9)).init()
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (batch, img, img, 3)).astype("float32")
    y = np.eye(1000, dtype="float32")[rng.integers(0, 1000, batch)]
    batch_t = net._batch_tuple(net._as_multi(DataSet(x, y)))
    step = net._make_train_step()
    key = jax.random.PRNGKey(0)
    it = np.int32(0)

    def one(params, state, opt, loss):
        return step(params, state, opt, batch_t, key, it)

    m = _make_measure(one, (net.params, net.state, net.opt_state, None),
                      steps, WARMUP, lambda a: a[3])
    if prep:
        return m
    return steps * batch / m()


def bench_flax_resnet50(batch=BATCH, img=IMG, steps=STEPS, prep=False,
                        dtype=None):
    import jax
    import jax.numpy as jnp
    import optax
    from flax import linen as nn

    dt = dtype or jnp.float32

    class Bottleneck(nn.Module):
        mid: int
        out: int
        stride: int = 1
        project: bool = False

        @nn.compact
        def __call__(self, x, train=True):
            r = x
            y = nn.Conv(self.mid, (1, 1), (self.stride, self.stride),
                        use_bias=False, dtype=dt)(x)
            y = nn.relu(nn.BatchNorm(use_running_average=not train)(y))
            y = nn.Conv(self.mid, (3, 3), padding="SAME",
                        use_bias=False, dtype=dt)(y)
            y = nn.relu(nn.BatchNorm(use_running_average=not train)(y))
            y = nn.Conv(self.out, (1, 1), use_bias=False, dtype=dt)(y)
            y = nn.BatchNorm(use_running_average=not train)(y)
            if self.project:
                r = nn.Conv(self.out, (1, 1), (self.stride, self.stride),
                            use_bias=False, dtype=dt)(x)
                r = nn.BatchNorm(use_running_average=not train)(r)
            return nn.relu(y + r)

    class ResNet50F(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = nn.Conv(64, (7, 7), (2, 2), padding="SAME",
                        use_bias=False, dtype=dt)(x)
            x = nn.relu(nn.BatchNorm(use_running_average=not train)(x))
            x = nn.max_pool(x, (3, 3), (2, 2), padding="SAME")
            for blocks, mid, out, stride in ((3, 64, 256, 1),
                                             (4, 128, 512, 2),
                                             (6, 256, 1024, 2),
                                             (3, 512, 2048, 2)):
                for b in range(blocks):
                    x = Bottleneck(mid, out,
                                   stride if b == 0 else 1,
                                   project=(b == 0))(x, train)
            x = jnp.mean(x, axis=(1, 2))
            return nn.Dense(1000)(x)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (batch, img, img, 3))
                    .astype("float32"))
    y = jnp.asarray(np.eye(1000, dtype="float32")[
        rng.integers(0, 1000, batch)])
    model = ResNet50F()
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    params = variables["params"]
    batch_stats = variables["batch_stats"]
    tx = optax.sgd(0.1, momentum=0.9, nesterov=True)
    opt = tx.init(params)

    @jax.jit
    def step(params, batch_stats, opt, loss_prev):
        def loss_fn(p):
            logits, upd = model.apply(
                {"params": p, "batch_stats": batch_stats}, x, train=True,
                mutable=["batch_stats"])
            return optax.softmax_cross_entropy(logits, y).mean(), upd
        (loss, upd), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
        u, opt2 = tx.update(g, opt, params)
        return optax.apply_updates(params, u), upd["batch_stats"], opt2, \
            loss

    m = _make_measure(lambda *a: step(*a),
                      (params, batch_stats, opt, None), steps, WARMUP,
                      lambda a: a[3])
    if prep:
        return m
    return steps * batch / m()


def bench_flax_resnet50_bf16(batch=BATCH, img=IMG, steps=STEPS,
                             prep=False):
    import jax.numpy as jnp
    return bench_flax_resnet50(batch, img, steps, prep,
                               dtype=jnp.bfloat16)


# ---------------------------------------------------------------------------
# 2. LeNet / MNIST training (BASELINE.md item 1)
# ---------------------------------------------------------------------------

def bench_ours_lenet(batch=LENET_BATCH, steps=LENET_STEPS,
                     prep=False):
    import jax
    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (ConvolutionLayer,
                                                   DenseLayer,
                                                   OutputLayer,
                                                   SubsamplingLayer)

    conf = (NeuralNetConfiguration.builder().set_seed(0)
            .updater(updaters.adam(1e-3)).list()
            .layer(ConvolutionLayer(n_out=20, kernel=(5, 5),
                                    activation="relu"))
            .layer(SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
            .layer(ConvolutionLayer(n_out=50, kernel=(5, 5),
                                    activation="relu"))
            .layer(SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
            .layer(DenseLayer(n_out=500, activation="relu"))
            .layer(OutputLayer(n_out=10, loss="mcxent"))
            .set_input_type(InputType.convolutional_flat(28, 28, 1))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (batch, 784)).astype("float32")
    y = np.eye(10, dtype="float32")[rng.integers(0, 10, batch)]
    batch_t = net._batch_tuple(DataSet(x, y))
    step = net._make_train_step()
    key = jax.random.PRNGKey(0)
    it = np.int32(0)

    def one(params, state, opt, loss):
        return step(params, state, opt, batch_t, key, it)

    m = _make_measure(one, (net.params, net.state, net.opt_state, None),
                      steps, WARMUP, lambda a: a[3])
    if prep:
        return m
    return steps * batch / min(m() for _ in range(3))


def bench_flax_lenet(batch=LENET_BATCH, steps=LENET_STEPS,
                     prep=False):
    import jax
    import jax.numpy as jnp
    import optax
    from flax import linen as nn

    class LeNet(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = x.reshape((x.shape[0], 28, 28, 1))
            x = nn.relu(nn.Conv(20, (5, 5), padding="VALID")(x))
            x = nn.max_pool(x, (2, 2), (2, 2))
            x = nn.relu(nn.Conv(50, (5, 5), padding="VALID")(x))
            x = nn.max_pool(x, (2, 2), (2, 2))
            x = x.reshape((x.shape[0], -1))
            x = nn.relu(nn.Dense(500)(x))
            return nn.Dense(10)(x)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (batch, 784)).astype("float32"))
    y = jnp.asarray(np.eye(10, dtype="float32")[
        rng.integers(0, 10, batch)])
    model = LeNet()
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    tx = optax.adam(1e-3)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt, loss_prev):
        def loss_fn(p):
            logits = model.apply({"params": p}, x)
            return optax.softmax_cross_entropy(logits, y).mean()
        loss, g = jax.value_and_grad(loss_fn)(params)
        u, opt2 = tx.update(g, opt, params)
        return optax.apply_updates(params, u), opt2, loss

    m = _make_measure(lambda *a: step(*a), (params, opt, None), steps,
                      WARMUP, lambda a: a[2])
    if prep:
        return m
    return steps * batch / min(m() for _ in range(3))


# ---------------------------------------------------------------------------
# 3. GravesLSTM char-RNN training (BASELINE.md item 3 — the lax.scan
#    path the reference accelerates with CudnnLSTMHelper)
# ---------------------------------------------------------------------------

CHAR_BATCH = 32
CHAR_T = 64
CHAR_VOCAB = 80
CHAR_HIDDEN = 256
CHAR_STEPS = 300


def bench_ours_char_rnn(batch=CHAR_BATCH, t=CHAR_T, vocab=CHAR_VOCAB,
                        hidden=CHAR_HIDDEN, steps=CHAR_STEPS,
                        prep=False):
    import jax
    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (GravesLSTM,
                                                   RnnOutputLayer)

    conf = (NeuralNetConfiguration.builder().set_seed(0)
            .updater(updaters.rmsprop(1e-3)).list()
            .layer(GravesLSTM(n_out=hidden, activation="tanh"))
            .layer(GravesLSTM(n_out=hidden, activation="tanh"))
            .layer(RnnOutputLayer(n_out=vocab, loss="mcxent"))
            .set_input_type(InputType.recurrent(vocab, t)).build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, (batch, t))
    x = np.eye(vocab, dtype="float32")[ids]
    y = np.eye(vocab, dtype="float32")[np.roll(ids, -1, axis=1)]
    batch_t = net._batch_tuple(DataSet(x, y))
    step = net._make_train_step()
    key = jax.random.PRNGKey(0)
    it = np.int32(0)

    def one(params, state, opt, loss):
        return step(params, state, opt, batch_t, key, it)

    m = _make_measure(one, (net.params, net.state, net.opt_state, None),
                      steps, WARMUP, lambda a: a[3])
    if prep:
        return m
    # chars (timesteps) per second
    return steps * batch * t / min(m() for _ in range(3))


def bench_flax_char_rnn(batch=CHAR_BATCH, t=CHAR_T, vocab=CHAR_VOCAB,
                        hidden=CHAR_HIDDEN, steps=CHAR_STEPS,
                        prep=False):
    """Hand-written flax/optax baseline: nn.scan over OptimizedLSTMCell
    ×2 + per-step softmax head — the idiomatic JAX char-RNN."""
    import jax
    import jax.numpy as jnp
    import optax
    from flax import linen as nn

    class CharRNN(nn.Module):
        @nn.compact
        def __call__(self, x):
            for i in range(2):
                x = nn.RNN(nn.OptimizedLSTMCell(hidden),
                           name=f"lstm{i}")(x)
            return nn.Dense(vocab)(x)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, (batch, t))
    x = jnp.asarray(np.eye(vocab, dtype="float32")[ids])
    y = jnp.asarray(np.eye(vocab, dtype="float32")[
        np.roll(ids, -1, axis=1)])
    model = CharRNN()
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    tx = optax.rmsprop(1e-3)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt, loss_prev):
        def loss_fn(p):
            logits = model.apply({"params": p}, x)
            return optax.softmax_cross_entropy(logits, y).mean()
        loss, g = jax.value_and_grad(loss_fn)(params)
        u, opt2 = tx.update(g, opt, params)
        return optax.apply_updates(params, u), opt2, loss

    m = _make_measure(lambda *a: step(*a), (params, opt, None), steps,
                      WARMUP, lambda a: a[2])
    if prep:
        return m
    return steps * batch * t / min(m() for _ in range(3))


# ---------------------------------------------------------------------------
# 4. Keras-imported VGG16 inference (BASELINE.md item 4)
# ---------------------------------------------------------------------------

VGG_BATCH = 32
VGG_STEPS = 60


_KERAS_VGG16_SCRIPT = r"""
import sys
import keras
from keras import layers
model = keras.Sequential(name="vgg16")
model.add(keras.Input((224, 224, 3)))
for block, (n, reps) in enumerate((
        (64, 2), (128, 2), (256, 3), (512, 3), (512, 3))):
    for r in range(reps):
        model.add(layers.Conv2D(n, 3, padding="same", activation="relu",
                                name=f"b{block}c{r}"))
    model.add(layers.MaxPooling2D(2, 2, name=f"b{block}p"))
model.add(layers.Flatten(name="flat"))
model.add(layers.Dense(4096, activation="relu", name="fc1"))
model.add(layers.Dense(4096, activation="relu", name="fc2"))
model.add(layers.Dense(1000, activation="softmax", name="pred"))
model.save(sys.argv[1])
"""


def _build_keras_vgg16(path):
    """Random-weight VGG16 saved in legacy h5 (no egress). Runs keras
    in a SUBPROCESS: importing TF into a process whose JAX already
    initialized the TPU deadlocks the h5 save."""
    import subprocess
    subprocess.run([sys.executable, "-c", _KERAS_VGG16_SCRIPT, path],
                   check=True, timeout=240,
                   env={**os.environ, "JAX_PLATFORMS": "cpu",
                        "CUDA_VISIBLE_DEVICES": ""})


def bench_keras_imported_vgg16(batch=VGG_BATCH, steps=VGG_STEPS,
                               prep=False):
    import jax

    from deeplearning4j_tpu.keras.importer import (
        import_keras_model_and_weights)

    import importlib.util
    if (importlib.util.find_spec("keras") is None
            or importlib.util.find_spec("h5py") is None):
        # clean dependency skip (rc 3 in leg mode), not a failure:
        # the build subprocess would die with CalledProcessError
        # otherwise
        raise ImportError("keras/h5py not installed")
    # cache the 554MB generated h5 across runs — the keras-subprocess
    # build is ~2 min of the leg and identical every time
    cache_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             ".bench_data")
    os.makedirs(cache_dir, exist_ok=True)
    path = os.path.join(cache_dir, "vgg16.h5")
    if not os.path.exists(path):
        # keras validates the extension, so the temp name must end .h5
        tmp = os.path.join(cache_dir, "vgg16.build-tmp.h5")
        _build_keras_vgg16(tmp)
        os.replace(tmp, path)
    net = import_keras_model_and_weights(path)
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (batch, 224, 224, 3)).astype("float32")
    out0 = net.output(x)            # builds + caches the jit
    jax.block_until_ready(out0)

    def m():
        return _time_infer(net.output, x, steps, 1)
    if prep:
        return m
    return steps * batch / m()


def bench_flax_vgg16_infer(batch=VGG_BATCH, steps=VGG_STEPS,
                           prep=False):
    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    class VGG16F(nn.Module):
        @nn.compact
        def __call__(self, x):
            for n, reps in ((64, 2), (128, 2), (256, 3), (512, 3),
                            (512, 3)):
                for _ in range(reps):
                    x = nn.relu(nn.Conv(n, (3, 3), padding="SAME")(x))
                x = nn.max_pool(x, (2, 2), (2, 2))
            x = x.reshape((x.shape[0], -1))
            x = nn.relu(nn.Dense(4096)(x))
            x = nn.relu(nn.Dense(4096)(x))
            return nn.softmax(nn.Dense(1000)(x))

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (batch, 224, 224, 3))
                    .astype("float32"))
    model = VGG16F()
    params = model.init(jax.random.PRNGKey(0), x)
    # params as an ARGUMENT, never a closure: closed-over arrays bake
    # into the HLO as literals — 554MB of constants for VGG16
    infer = jax.jit(model.apply)

    def fn(x):
        return infer(params, x)

    def m():
        return _time_infer(fn, x, steps, 1)
    if prep:
        return m
    return steps * batch / m()


# ---------------------------------------------------------------------------
# analytic model FLOPs for MFU
# ---------------------------------------------------------------------------

RESNET50_FWD_FLOPS = 4.09e9        # per 224x224 image (2*MACs)
VGG16_FWD_FLOPS = 15.47e9
LENET_FWD_FLOPS = 4.6e6
# GravesLSTM step: 4 gates × (in+hidden+peep) ≈ 2*4*h*(in+h) MACs/cell
_CH = CHAR_HIDDEN
CHAR_RNN_FWD_FLOPS_PER_CHAR = (
    2 * 4 * _CH * (CHAR_VOCAB + _CH)          # layer 1
    + 2 * 4 * _CH * (_CH + _CH)               # layer 2
    + 2 * _CH * CHAR_VOCAB)                   # head
TRAIN_MULT = 3.0                    # bwd ≈ 2× fwd


def _mfu(per_item_fwd_flops, items_per_sec, train, peak):
    if peak is None:
        return None
    flops = per_item_fwd_flops * (TRAIN_MULT if train else 1.0)
    return items_per_sec * flops / peak



# ---------------------------------------------------------------------------
# legs — each returns one result dict. The runner gives each leg its
# own SUBPROCESS (``--leg NAME``), one at a time: a leg holds the chip
# while it runs, gets its own timeout, and a crashed leg cannot take
# the others down. The persistent XLA cache keeps repeat compiles fast.
# ---------------------------------------------------------------------------

def _check_plausible(mfu_like, what):
    """A timing that implies >90% of peak measured the enqueue, not
    the work (a missing end-of-burst fetch): fail the leg rather than
    print it."""
    if mfu_like is not None and mfu_like > 0.9:
        raise RuntimeError(
            f"implausible timing for {what}: implied MFU "
            f"{mfu_like:.2f} — the burst was not synchronized")


def _leg_resnet_f32(peak):
    m_ours = bench_ours(prep=True)
    m_ref = bench_flax_resnet50(prep=True)
    dt_o, dt_r = _interleave(m_ours, m_ref, repeats=2)
    ours = STEPS * BATCH / dt_o
    ref = STEPS * BATCH / dt_r
    print(f"resnet50 ours: {ours:.1f} img/s, flax ref: {ref:.1f}",
          file=sys.stderr)
    if peak:
        _check_plausible(_mfu(RESNET50_FWD_FLOPS, max(ours, ref), True,
                              peak), "resnet50 f32")
    return {
        "metric": "ResNet50 train throughput (batch 128, 224x224, f32)",
        "value": round(ours, 1), "unit": "images/sec/chip",
        "baseline": round(ref, 1), "vs_baseline": round(ours / ref, 3),
        "mfu": round(_mfu(RESNET50_FWD_FLOPS, ours, True, peak), 4)
        if peak else None}


def _leg_resnet_bf16(peak):
    from deeplearning4j_tpu import dtypes
    with dtypes.policy_scope(dtypes.tpu_bf16()):
        m_ours = bench_ours(prep=True)
    m_ref = bench_flax_resnet50_bf16(prep=True)
    dt_o, dt_r = _interleave(m_ours, m_ref, repeats=3)
    ours16 = STEPS * BATCH / dt_o
    ref16 = STEPS * BATCH / dt_r
    print(f"resnet50 bf16 ours: {ours16:.1f} img/s, flax bf16: "
          f"{ref16:.1f}", file=sys.stderr)
    if peak:
        _check_plausible(_mfu(RESNET50_FWD_FLOPS, max(ours16, ref16),
                              True, peak), "resnet50 bf16")
    return {
        "metric": ("ResNet50 train throughput bf16 compute (batch "
                   "128, 224x224)"),
        "value": round(ours16, 1), "unit": "images/sec/chip",
        "baseline": round(ref16, 1),
        "vs_baseline": round(ours16 / ref16, 3),
        "mfu": round(_mfu(RESNET50_FWD_FLOPS, ours16, True, peak), 4)
        if peak else None,
        "note": ("ours: bf16 compute AND bf16 hidden activations "
                 "(f32 params/BN-stats/logits); baseline: flax "
                 "modules with dtype=bfloat16")}


def _leg_lenet(peak):
    m_ours = bench_ours_lenet(prep=True)
    m_ref = bench_flax_lenet(prep=True)
    # repeats=6: LeNet compute is ~1ms/step, so this leg times the
    # dispatch path, not the MXU — more interleaved bursts tighten
    # the min
    dt_o, dt_r = _interleave(m_ours, m_ref, repeats=6)
    lenet = LENET_STEPS * LENET_BATCH / dt_o
    lenet_ref = LENET_STEPS * LENET_BATCH / dt_r
    print(f"lenet ours: {lenet:.0f} img/s, flax: {lenet_ref:.0f}",
          file=sys.stderr)
    if peak:
        _check_plausible(_mfu(LENET_FWD_FLOPS, max(lenet, lenet_ref),
                              True, peak), "lenet")
    return {
        "metric": "LeNet MNIST train throughput (batch 128)",
        "value": round(lenet, 0), "unit": "images/sec/chip",
        "baseline": round(lenet_ref, 0),
        "vs_baseline": round(lenet / lenet_ref, 3),
        "mfu": round(_mfu(LENET_FWD_FLOPS, lenet, True, peak), 5)
        if peak else None,
        "note": ("dispatch-bound leg (~1 ms/step of compute): the "
                 "ratio carries host dispatch jitter")}


def _leg_char_rnn(peak):
    m_ours = bench_ours_char_rnn(prep=True)
    m_ref = bench_flax_char_rnn(prep=True)
    dt_o, dt_r = _interleave(m_ours, m_ref, repeats=3)
    chars = CHAR_STEPS * CHAR_BATCH * CHAR_T / dt_o
    chars_ref = CHAR_STEPS * CHAR_BATCH * CHAR_T / dt_r
    print(f"char-rnn ours: {chars:.0f} chars/s, flax scan: "
          f"{chars_ref:.0f}", file=sys.stderr)
    if peak:
        _check_plausible(_mfu(CHAR_RNN_FWD_FLOPS_PER_CHAR,
                              max(chars, chars_ref), True, peak),
                         "char-rnn")
    return {
        "metric": ("GravesLSTM char-RNN train throughput (batch "
                   f"{CHAR_BATCH}, T={CHAR_T}, 2x{CHAR_HIDDEN}, "
                   f"vocab {CHAR_VOCAB})"),
        "value": round(chars, 0), "unit": "chars/sec/chip",
        "baseline": round(chars_ref, 0),
        "vs_baseline": round(chars / chars_ref, 3),
        "mfu": round(_mfu(CHAR_RNN_FWD_FLOPS_PER_CHAR, chars, True,
                          peak), 5) if peak else None,
        "note": ("ours = GravesLSTM (peepholes: +25% gate FLOPs); "
                 "baseline = flax OptimizedLSTMCell nn.scan")}


def _leg_vgg16_import(peak):
    m_ours = bench_keras_imported_vgg16(prep=True)
    m_ref = bench_flax_vgg16_infer(prep=True)
    # repeats=3: HLO analysis showed ours and flax compile to
    # IDENTICAL work (flops 9.591e11, bytes 4.654e9, both to 4
    # digits), so the ratio is timing noise around parity — take the
    # min over more interleaved bursts
    dt_o, dt_r = _interleave(m_ours, m_ref, repeats=3)
    vgg = VGG_STEPS * VGG_BATCH / dt_o
    vgg_ref = VGG_STEPS * VGG_BATCH / dt_r
    print(f"vgg16 infer ours(keras-import): {vgg:.1f} img/s, "
          f"flax: {vgg_ref:.1f}", file=sys.stderr)
    if peak:
        _check_plausible(_mfu(VGG16_FWD_FLOPS, max(vgg, vgg_ref),
                              False, peak), "vgg16")
    return {
        "metric": ("Keras-imported VGG16 inference (batch "
                   f"{VGG_BATCH}, 224x224, f32)"),
        "value": round(vgg, 1), "unit": "images/sec/chip",
        "baseline": round(vgg_ref, 1),
        "vs_baseline": round(vgg / vgg_ref, 3),
        "mfu": round(_mfu(VGG16_FWD_FLOPS, vgg, False, peak), 4)
        if peak else None,
        "note": ("ours and the flax reference compile to identical "
                 "XLA work — cost_analysis flops 9.591e11 and "
                 "bytes-accessed 4.654e9 match to 4 digits — so a "
                 "ratio away from 1.0 on this leg is timing noise, "
                 "not a framework cost")}


def _ensure_png_tree(root, n_classes=10, per_class=52, hw=224):
    """Directory-per-label PNG tree for the ETL leg (cached across
    runs; ~78MB of noise PNGs — noise compresses worst, so decode
    cost is an upper bound)."""
    import json
    stamp = os.path.join(root, "stamp.json")
    want = {"n_classes": n_classes, "per_class": per_class, "hw": hw}
    if os.path.exists(stamp):
        with open(stamp) as f:
            if json.load(f) == want:
                return root
    if os.path.isdir(root):
        # outdated or half-generated tree (config mismatch, or a run
        # killed before the stamp was written): clear it, or leftover
        # files silently inflate the dataset the numbers claim
        import shutil
        shutil.rmtree(root)
    from PIL import Image
    rng = np.random.default_rng(0)
    for c in range(n_classes):
        d = os.path.join(root, f"class{c}")
        os.makedirs(d, exist_ok=True)
        for i in range(per_class):
            a = rng.integers(0, 256, (hw, hw, 3), dtype=np.uint8)
            Image.fromarray(a).save(os.path.join(d, f"im{i}.png"))
    with open(stamp, "w") as f:
        json.dump(want, f)
    return root


def _leg_resnet_native_etl(peak):
    """Train ResNet50 FROM A PNG TREE through the native libpng worker
    pool (reference RecordReaderDataSetIterator.java:52 +
    AsyncDataSetIterator.java:30 — 'the device never waits'). Round-5
    shape (round-4 verdict next #2): measure (a) decode-thread
    scaling, (b) the decode-ahead OVERLAP with a device-free
    simulated compute consumer — proving the bounded queue hides
    decode latency behind any compute >= decode, (c) the per-batch
    host->device upload in isolation, then (d) the honest end-to-end
    number with the exposure attributed."""
    from deeplearning4j_tpu.data.native_loader import (
        NativeImageDataSetIterator, native_image_available)
    if not native_image_available():
        raise ImportError("native image loader unavailable (g++/libpng)")
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.zoo import ResNet50

    data_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            ".bench_data")
    tree = _ensure_png_tree(os.path.join(data_dir, "png_tree_224"))
    batch = 128
    host_cores = os.cpu_count() or 1

    def make_it(nt=4):
        # ONE loader config for every measured section — decode,
        # overlap, warmup and e2e must describe the same pipeline
        return NativeImageDataSetIterator(tree, batch, 224, 224, 3,
                                          n_threads=nt,
                                          queue_capacity=4)

    def decode_pass(nt, consume_sleep_s=0.0):
        """STEADY-STATE decode ms/full-batch at n_threads=nt (first
        batch dropped: it pays pool spin-up + directory scan), min of
        2 passes. With consume_sleep_s the consumer simulates a
        device step that long (sleep holds no GIL and no core, so the
        worker pool decodes ahead into the queue — measuring what the
        queue can HIDE, with no device in the loop)."""
        best = float("inf")
        for _ in range(2):
            it = make_it(nt)
            gaps = []
            last = time.perf_counter()
            for ds in it:
                if ds.num_examples() == batch:
                    now = time.perf_counter()
                    gaps.append(now - last)
                    if consume_sleep_s:
                        time.sleep(consume_sleep_s)
                    last = time.perf_counter()
            if len(gaps) > 1:
                gaps = gaps[1:]
            dt = sum(gaps) / max(1, len(gaps)) * 1e3
            best = min(best, dt)
        return best

    # (a) decode scaling over worker counts (on a 1-core host this is
    # flat by construction — that IS the measured evidence that the
    # host, not the loader, is the ceiling here)
    scaling = {nt: round(decode_pass(nt), 1) for nt in (1, 2, 4)}
    decode_ms = scaling[4]

    # (b) overlap proof: consumer sleeps decode_ms per batch (a
    # stand-in for any device step >= decode). With consume_sleep_s
    # set, decode_pass times only the post-step wait + batch
    # materialization — the EXPOSED ETL under overlap directly; a
    # small constant (the consumer-side memcpy of the 60MB batch)
    # proves the queue hides the actual DECODE entirely.
    exposed_sim = decode_pass(4, consume_sleep_s=decode_ms / 1e3)
    # slack case (step = 2x decode): on a host with ANY headroom the
    # exposure floor is just the batch hand-off, proving the queue
    # hides the decode itself
    exposed_slack = decode_pass(4, consume_sleep_s=2 * decode_ms / 1e3)

    # (c) + (d): the real device path
    net = ResNet50(n_classes=10, input_shape=(224, 224, 3),
                   updater=updaters.nesterovs(0.1, 0.9)).init()
    step = net._make_train_step()
    key = jax.random.PRNGKey(0)
    first = next(iter(make_it()))
    bt = net._batch_tuple(net._as_multi(first))
    p, s, o, loss = step(net.params, net.state, net.opt_state, bt, key,
                         np.int32(0))
    float(jnp.sum(loss))

    # (c) upload tax in isolation: host->device transfer of one
    # batch's features (fresh numpy each time so nothing caches)
    up = float("inf")
    feats = np.asarray(first.features[0] if isinstance(
        first.features, (list, tuple)) else first.features)
    for i in range(3):
        fresh = feats + np.float32(i + 1)       # defeat content dedupe
        t0 = time.perf_counter()
        a = jax.device_put(fresh)
        # minimal data-dependent fetch as the sync: a full jnp.sum
        # would bill a 77MB on-device reduction to the 'upload tax'
        float(a[0, 0, 0, 0])
        up = min(up, time.perf_counter() - t0)
    upload_ms = up * 1e3

    # pure step: cached batch burst
    t0 = time.perf_counter()
    for _ in range(10):
        p, s, o, loss = step(p, s, o, bt, key, np.int32(0))
    float(jnp.sum(loss))
    step_ms = (time.perf_counter() - t0) / 10 * 1e3

    # (d) end-to-end epochs from PNGs
    n_img = 0
    it = make_it()
    t0 = time.perf_counter()
    for _ in range(2):
        for ds in it:
            if ds.num_examples() != batch:
                continue
            bt2 = net._batch_tuple(net._as_multi(ds))
            p, s, o, loss = step(p, s, o, bt2, key, np.int32(0))
            n_img += batch
    float(jnp.sum(loss))
    e2e = time.perf_counter() - t0
    e2e_ms = e2e / (n_img / batch) * 1e3
    rate = n_img / e2e
    exposed = max(0.0, e2e_ms - step_ms)
    print(f"native-etl: decode scaling {scaling} ms/batch, "
          f"overlap-exposed {exposed_sim:.1f} ms (at 2x step: "
          f"{exposed_slack:.1f}), upload {upload_ms:.1f} ms, step "
          f"{step_ms:.1f} ms, e2e {e2e_ms:.1f} ms/batch "
          f"({rate:.1f} img/s), cores {host_cores}", file=sys.stderr)
    return {
        "metric": ("ResNet50 train-from-PNG-tree via native ETL "
                   "(batch 128, 224x224, f32)"),
        "value": round(rate, 1), "unit": "images/sec/chip",
        "baseline": None, "vs_baseline": None,
        "decode_ms_per_batch_by_threads": scaling,
        "overlap_exposed_ms_per_batch": round(exposed_sim, 1),
        "overlap_exposed_ms_at_2x_step": round(exposed_slack, 1),
        "upload_ms_per_batch": round(upload_ms, 1),
        "step_ms_per_batch": round(step_ms, 1),
        "e2e_ms_per_batch": round(e2e_ms, 1),
        "exposed_etl_ms_per_batch": round(exposed, 1),
        "host_cores": host_cores,
        "note": ("overlap_exposed = measured post-step wait + batch "
                 "hand-off under a GIL-free simulated step (no device "
                 "in the loop): at step=decode a 1-core host is "
                 "saturated (decode competes with the consumer), at "
                 "step=2x decode the exposure drops to the hand-off "
                 "floor — the bounded queue hides the DECODE itself "
                 "(AsyncDataSetIterator.java:30 'device never "
                 "waits'). Round 5 removed the consumer-side second "
                 "copy (fresh per-batch arrays, native memcpy only). "
                 "The e2e gap beyond step_ms decomposes into "
                 "upload_ms (the ~77MB/batch host->device transfer) "
                 "plus unhidden decode on "
                 "this host; the 1->2->4 thread scaling table "
                 "documents whether cores or the loader are the "
                 "ceiling (flat scaling on a 1-core host = "
                 "host-bound by construction)")}


LM_B, LM_T, LM_D, LM_L, LM_H, LM_V = 8, 1024, 1024, 8, 16, 2048
LM_STEPS = 20
# causal-corrected model FLOPs per token, forward: per layer 24*D^2
# (qkv/o/mlp matmuls) + 2*T*D (causal attention: half the T^2 tiles),
# plus the 2*D*V head; embedding gather ~0. Train = 3x forward.
LM_FWD_FLOPS_PER_TOK = LM_L * (24 * LM_D * LM_D + 2 * LM_T * LM_D) \
    + 2 * LM_D * LM_V


def bench_ours_transformer_lm(prep=False):
    """Config-built decoder-only LM through the framework surface:
    EmbeddingSequence + 8 pre-LN TransformerEncoderLayers (causal
    flash kernels) + RnnOutputLayer, bf16 compute policy — the
    high-MFU showcase (round-3 verdict weak #2)."""
    import jax

    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration, dtypes)
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (
        EmbeddingSequenceLayer, RnnOutputLayer, TransformerEncoderLayer)

    b = (NeuralNetConfiguration.builder().set_seed(0)
         .updater(updaters.adam(1e-3)).list()
         .layer(EmbeddingSequenceLayer(n_in=LM_V, n_out=LM_D)))
    for _ in range(LM_L):
        b = b.layer(TransformerEncoderLayer(n_heads=LM_H, causal=True))
    conf = (b.layer(RnnOutputLayer(n_out=LM_V, loss="mcxent"))
            .set_input_type(InputType.recurrent(LM_V, LM_T)).build())
    with dtypes.policy_scope(dtypes.tpu_bf16()):
        net = MultiLayerNetwork(conf).init()
        rng = np.random.default_rng(0)
        ids = rng.integers(0, LM_V, (LM_B, LM_T)).astype("float32")
        y = np.eye(LM_V, dtype="float32")[
            rng.integers(0, LM_V, (LM_B, LM_T))]
        batch_t = net._batch_tuple(DataSet(ids, y))
        step = net._make_train_step()
        key = jax.random.PRNGKey(0)
        it = np.int32(0)

        def one(params, state, opt, loss):
            return step(params, state, opt, batch_t, key, it)

        m = _make_measure(one, (net.params, net.state, net.opt_state,
                                None), LM_STEPS, WARMUP,
                          lambda a: a[3])
    if prep:
        return m
    return LM_STEPS * LM_B * LM_T / m()


def bench_flax_transformer_lm(prep=False):
    """The same pre-LN decoder in flax linen (nn.SelfAttention with a
    causal mask — XLA-fused exact attention), bf16 module dtype."""
    import jax
    import jax.numpy as jnp
    import optax
    from flax import linen as nn

    dt = jnp.bfloat16

    class Block(nn.Module):
        @nn.compact
        def __call__(self, x):
            h = nn.LayerNorm(dtype=dt)(x)
            h = nn.SelfAttention(
                num_heads=LM_H, dtype=dt, deterministic=True)(
                h, mask=nn.make_causal_mask(
                    jnp.ones((x.shape[0], x.shape[1]))))
            x = x + h
            h = nn.LayerNorm(dtype=dt)(x)
            h = nn.Dense(4 * LM_D, dtype=dt)(h)
            h = nn.gelu(h)
            h = nn.Dense(LM_D, dtype=dt)(h)
            return x + h

    class LM(nn.Module):
        @nn.compact
        def __call__(self, ids):
            x = nn.Embed(LM_V, LM_D, dtype=dt)(ids)
            for _ in range(LM_L):
                x = Block()(x)
            return nn.Dense(LM_V, dtype=dt)(x)

    model = LM()
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, LM_V, (LM_B, LM_T)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, LM_V, (LM_B, LM_T)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    tx = optax.adam(1e-3)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt, loss_prev):
        def loss_fn(p):
            logits = model.apply(p, ids).astype(jnp.float32)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()
        loss, g = jax.value_and_grad(loss_fn)(params)
        up, opt2 = tx.update(g, opt, params)
        return optax.apply_updates(params, up), opt2, loss

    m = _make_measure(step, (params, opt, None),
                      LM_STEPS, WARMUP, lambda a: a[2])
    if prep:
        return m
    return LM_STEPS * LM_B * LM_T / m()


def _leg_transformer_lm(peak):
    m_ours = bench_ours_transformer_lm(prep=True)
    m_ref = bench_flax_transformer_lm(prep=True)
    dt_o, dt_r = _interleave(m_ours, m_ref, repeats=3)
    toks = LM_STEPS * LM_B * LM_T
    ours = toks / dt_o
    ref = toks / dt_r
    print(f"transformer-lm ours(flash,bf16): {ours:.0f} tok/s, flax "
          f"(exact attn,bf16): {ref:.0f}", file=sys.stderr)
    if peak:
        _check_plausible(_mfu(LM_FWD_FLOPS_PER_TOK, max(ours, ref),
                              True, peak), "transformer-lm")
    return {
        "metric": (f"Transformer-LM train throughput (B={LM_B}, "
                   f"T={LM_T}, d={LM_D}, L={LM_L}, heads={LM_H}, "
                   f"vocab {LM_V}, bf16)"),
        "value": round(ours, 0), "unit": "tokens/sec/chip",
        "baseline": round(ref, 0),
        "vs_baseline": round(ours / ref, 3),
        "mfu": round(_mfu(LM_FWD_FLOPS_PER_TOK, ours, True, peak), 4)
        if peak else None,
        "note": ("ours: config-built MLN (EmbeddingSequence + 8 "
                 "causal TransformerEncoderLayers + RnnOutputLayer), "
                 "Pallas flash kernels, bf16 policy; baseline: same "
                 "arch in flax linen, nn.SelfAttention causal-masked "
                 "exact attention, bf16; causal-corrected model "
                 "FLOPs (attention counted at T^2/2)")}


def _leg_flash_attention(peak):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.attention import flash_attention
    B, T, H, D = 4, 4096, 8, 64
    rngk = jax.random.PRNGKey(0)
    q = jax.random.normal(rngk, (B, T, H, D), jnp.float32)

    def naive(q, k, v):
        qh = jnp.swapaxes(q, 1, 2)
        kh = jnp.swapaxes(k, 1, 2)
        vh = jnp.swapaxes(v, 1, 2)
        s = qh @ jnp.swapaxes(kh, -1, -2) / np.sqrt(D)
        return jnp.swapaxes(jax.nn.softmax(s) @ vh, 1, 2)

    def mk(fn):
        # CHAIN the gradient through the next input, so the final
        # fetch depends on every step. grad(q) has q's shape, so it
        # feeds back.
        g = jax.jit(jax.grad(lambda x: jnp.sum(fn(x, x, x) ** 2)))
        float(jnp.sum(g(q)))                # compile + warm (fetch-sync)

        def measure():
            # 100 chained steps per burst; min-of-N by the caller;
            # host FETCH as the end-of-burst sync
            a = q
            t0 = time.perf_counter()
            for _ in range(100):
                a = g(a)
            float(jnp.sum(a))
            return (time.perf_counter() - t0) / 100
        return measure

    m_flash = mk(lambda a, b, c: flash_attention(a, b, c))
    m_naive = mk(naive)
    dt_f, dt_n = _interleave(m_flash, m_naive, repeats=3)
    toks = B * T
    attn_flops = 14 * T * T * D * B * H

    # the REAL bar (round-3 verdict weak #3): JAX's bundled production
    # TPU flash kernel, given the same 1024^2 tiles ours auto-selects
    # (its defaults — 128-col k blocks — are 5x slower at this config,
    # so tuning it is the fair comparison). Seam contract = fastest
    # algorithm (reference CudnnConvolutionHelper.java:156-192).
    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            BlockSizes)
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as prod_flash)
        bs = BlockSizes(
            block_q=1024, block_k_major=1024, block_k=1024, block_b=1,
            block_q_major_dkv=1024, block_k_major_dkv=1024,
            block_k_dkv=1024, block_q_dkv=1024,
            block_k_major_dq=1024, block_k_dq=1024, block_q_dq=1024)

        def prod(a, b, c):
            ah, bh, ch = (jnp.swapaxes(x, 1, 2) for x in (a, b, c))
            o = prod_flash(ah, bh, ch, sm_scale=1.0 / np.sqrt(D),
                           block_sizes=bs)
            return jnp.swapaxes(o, 1, 2)

        m_prod = mk(prod)
        # interleave against OURS in its own window (host drift
        # between windows lands asymmetrically, so each ratio comes
        # from alternating bursts within ONE window): vs_baseline
        # stays (dt_f, dt_n) from window 1, vs_production_kernel is
        # (dt_f2, dt_p) from window 2 — dt_f2 is NOT folded into the
        # headline value
        dt_f2, dt_p = _interleave(m_flash, m_prod, repeats=3)
        prod_ratio = dt_p / dt_f2
        prod_note = (f"vs jax.experimental.pallas.ops.tpu."
                     f"flash_attention (tuned to the same 1024^2 "
                     f"tiles): ours {prod_ratio:.3f}x its speed")
        print(f"flash vs production kernel: ours {toks/dt_f2:.0f} "
              f"tok/s, prod {toks/dt_p:.0f} tok/s "
              f"(ours/prod {prod_ratio:.3f}x)", file=sys.stderr)
    except Exception as e:           # older jax layouts: informational
        dt_f2 = dt_p = None
        prod_ratio = None
        prod_note = f"production-kernel comparison unavailable: {e}"
    if peak and dt_p is not None:
        # OUTSIDE the except: an unsynchronized window must fail the
        # leg, not demote to a note
        _check_plausible(attn_flops / dt_p / peak,
                         "flash production-kernel baseline")
        _check_plausible(attn_flops / dt_f2 / peak,
                         "flash (production-comparison window)")
    print(f"flash attention T=4096 fwd+bwd: {toks/dt_f:.0f} "
          f"tok/s vs naive {toks/dt_n:.0f}", file=sys.stderr)
    if peak:
        _check_plausible(attn_flops / min(dt_f, dt_n) / peak,
                         "flash attention")
    return {
        "metric": ("flash attention fwd+bwd (B=4, T=4096, "
                   "H=8, D=64, f32)"),
        "value": round(toks / dt_f, 0), "unit": "tokens/sec",
        "baseline": round(toks / dt_n, 0),
        "vs_baseline": round(dt_n / dt_f, 3),
        "vs_production_kernel": (round(prod_ratio, 3)
                                 if prod_ratio is not None else None),
        "mfu": round(attn_flops / dt_f / peak, 4) if peak else None,
        "note": ("baseline = naive attention (materializes TxT); "
                 "both at XLA default matmul precision; Pallas "
                 "fwd+bwd kernels, auto 1024^2 tiles; " + prod_note)}


SERVE_CONC = 32           # closed-loop clients
SERVE_REQUESTS = 1536     # total requests through the scheduler
SERVE_SEQ_REQUESTS = 256  # sequential-baseline sample


def _leg_serving_throughput(peak):
    """The serving subsystem's in-process number (no HTTP in the
    loop): requests/sec and tail latency at fixed concurrency through
    ``serving.BatchScheduler`` — SERVE_CONC closed-loop clients each
    firing 1-row predicts back-to-back — vs the same model called
    sequentially one request at a time (what a front end without
    dynamic batching would do). The ratio is the value of coalescing
    concurrent requests into few large, shape-stable device calls."""
    import threading

    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                   OutputLayer)
    from deeplearning4j_tpu.serving.metrics import ServingMetrics
    from deeplearning4j_tpu.serving.scheduler import BatchScheduler

    feat, hidden, classes, max_bs = 32, 128, 16, 64
    conf = (NeuralNetConfiguration.builder().set_seed(0)
            .updater(updaters.adam(1e-3)).list()
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_out=classes, loss="mcxent"))
            .set_input_type(InputType.feed_forward(feat)).build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    xs = rng.normal(0, 1, (SERVE_CONC, 1, feat)).astype("float32")

    # warm every power-of-two batch shape the scheduler can emit, so
    # the measured window holds zero compiles
    s = 1
    while s <= max_bs:
        np.asarray(net.output(np.zeros((s, feat), np.float32)))
        s *= 2

    # sequential baseline: one request at a time, no coalescing
    t0 = time.perf_counter()
    for i in range(SERVE_SEQ_REQUESTS):
        np.asarray(net.output(xs[i % SERVE_CONC]))
    seq_rps = SERVE_SEQ_REQUESTS / (time.perf_counter() - t0)

    metrics = ServingMetrics()
    sched = BatchScheduler(net, max_batch_size=max_bs,
                           queue_limit=4 * SERVE_CONC, wait_ms=1.0,
                           metrics=metrics)
    per_client = SERVE_REQUESTS // SERVE_CONC
    errs = []

    def client(c):
        try:
            for _ in range(per_client):
                sched.predict(xs[c])
        except BaseException as e:      # surfaced below, fails the leg
            errs.append(e)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(SERVE_CONC)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    sched.shutdown()
    if errs:
        raise errs[0]
    served = per_client * SERVE_CONC
    rps = served / dt
    snap = metrics.snapshot()
    ep = snap["endpoints"]["predict"]
    occ = snap["batching"]["predict"]
    print(f"serving: {rps:.0f} req/s at {SERVE_CONC} clients "
          f"(p50 {ep['latency']['p50_ms']:.1f} ms, p99 "
          f"{ep['latency']['p99_ms']:.1f} ms, avg batch "
          f"{occ['avg_batch_size']:.1f}); sequential {seq_rps:.0f} "
          "req/s", file=sys.stderr)
    return {
        "metric": (f"serving scheduler throughput (closed loop, "
                   f"{SERVE_CONC} clients, 1-row requests, MLP "
                   f"{feat}-{hidden}-{hidden}-{classes})"),
        "value": round(rps, 1), "unit": "requests/sec",
        "baseline": round(seq_rps, 1),
        "vs_baseline": round(rps / seq_rps, 3),
        "p50_ms": ep["latency"]["p50_ms"],
        "p99_ms": ep["latency"]["p99_ms"],
        "avg_batch_size": occ["avg_batch_size"],
        "max_batch_size_seen": occ["max_batch_size_seen"],
        "mfu": None,
        "note": ("value: serving.BatchScheduler (dynamic batching, "
                 "pow2 shape buckets, 1 ms window) under "
                 f"{SERVE_CONC} concurrent closed-loop clients; "
                 "baseline: the same model called one request at a "
                 "time — the no-batching front end. All compiled "
                 "shapes pre-warmed; in-process, no HTTP")}


TRACE_SAMPLE_RATES = (0.0, 0.01, 1.0)
TRACE_OVERHEAD_BAR = 0.02      # ≤2% throughput cost at 1% sampling


def _leg_tracing_overhead(peak):
    """What request-scoped tracing costs the serving hot path: the
    serving_throughput harness re-run at head-sampling 0% / 1% /
    100%. Every request carries a RequestContext (the phase ledger
    feeds the attribution histograms unconditionally); sampling only
    gates span EMISSION — so the 1%-vs-0% delta is the number the
    default config actually pays. Bar: ≤2% at 1% sampling."""
    import threading

    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                   OutputLayer)
    from deeplearning4j_tpu.observability.tracing import (
        RequestContext, Sampler, trace)
    from deeplearning4j_tpu.serving.metrics import ServingMetrics
    from deeplearning4j_tpu.serving.scheduler import BatchScheduler

    feat, hidden, classes, max_bs = 32, 128, 16, 64
    conf = (NeuralNetConfiguration.builder().set_seed(0)
            .updater(updaters.adam(1e-3)).list()
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_out=classes, loss="mcxent"))
            .set_input_type(InputType.feed_forward(feat)).build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    xs = rng.normal(0, 1, (SERVE_CONC, 1, feat)).astype("float32")
    s = 1
    while s <= max_bs:
        np.asarray(net.output(np.zeros((s, feat), np.float32)))
        s *= 2

    def run_at(rate):
        sampler = Sampler(rate=rate)
        metrics = ServingMetrics()
        sched = BatchScheduler(net, max_batch_size=max_bs,
                               queue_limit=4 * SERVE_CONC,
                               wait_ms=1.0, metrics=metrics)
        per_client = SERVE_REQUESTS // SERVE_CONC
        errs = []

        def client(c):
            try:
                for _ in range(per_client):
                    ctx = RequestContext.new(
                        "/v1/predict", sampler)
                    sched.predict(xs[c], ctx=ctx)
            except BaseException as e:
                errs.append(e)

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(SERVE_CONC)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        sched.shutdown()
        if errs:
            raise errs[0]
        trace.clear()     # don't let the 100% run's buffer linger
        return per_client * SERVE_CONC / dt

    # PAIRED back-to-back runs, median of ratios: single-run
    # scheduler throughput swings ±50% on a noisy host and the drift
    # is not monotone, so best-of / averaged absolute numbers charge
    # machine weather to whichever rate ran at the wrong time. A
    # ratio within one adjacent pair cancels the drift; the median
    # over pairs (with pair order alternating) is robust to the
    # outlier rounds. This is the same drift problem the interleaved
    # bench_ours/bench_ref measurement solves, at percent scale.
    import statistics

    def paired_ratio(rate, pairs=6):
        ratios = []
        for i in range(pairs):
            if i % 2 == 0:
                base, test = run_at(0.0), run_at(rate)
            else:
                test, base = run_at(rate), run_at(0.0)
            ratios.append(test / base)
        return statistics.median(ratios)

    rel_1pct = paired_ratio(0.01)
    rel_full = paired_ratio(1.0)
    rps_base = run_at(0.0)
    overhead_1pct = max(0.0, 1.0 - rel_1pct)
    overhead_full = max(0.0, 1.0 - rel_full)
    print(f"tracing overhead: ~{rps_base:.0f} req/s; 1% sampling "
          f"{rel_1pct:.3f}x of unsampled "
          f"({overhead_1pct * 100:.1f}% cost), 100% sampling "
          f"{rel_full:.3f}x ({overhead_full * 100:.1f}% cost)",
          file=sys.stderr)
    return {
        "metric": (f"request-tracing overhead (serving scheduler, "
                   f"{SERVE_CONC} closed-loop clients, 1-row "
                   "requests)"),
        "value": round(rel_1pct, 3),
        "unit": "throughput ratio (1% sampling / unsampled)",
        "baseline": 1.0,
        "vs_baseline": round(rel_1pct, 3),
        "rps_unsampled": round(rps_base, 1),
        "ratio_sampled_100pct": round(rel_full, 3),
        "overhead_at_1pct": round(overhead_1pct, 4),
        "overhead_at_100pct": round(overhead_full, 4),
        "bar_overhead_at_1pct": TRACE_OVERHEAD_BAR,
        "passed_bar": bool(overhead_1pct <= TRACE_OVERHEAD_BAR),
        "mfu": None,
        "note": ("serving_throughput harness with every request "
                 "carrying a RequestContext; sampling gates span "
                 "emission only (phase ledger + attribution "
                 "histograms record at EVERY rate). Median of 6 "
                 "paired back-to-back ratios, pair order "
                 "alternating — drift-robust on noisy hosts; "
                 "bar: ≤2% cost at 1% sampling")}


ROUTER_CONC = 16          # closed-loop clients against the router
ROUTER_REQUESTS = 600     # per fleet size


def _leg_router_fleet(peak):
    """The fleet's robustness headline: sustained QPS and p99
    through the health-aware router at N=1 vs N=4 SUBPROCESS
    replicas (real processes — no shared GIL, and the SIGKILL is a
    literal signal 9), then N=4 again with one replica killed
    mid-run by a seeded ``serving.replica`` chaos fault. The kill
    run must drop ZERO requests (failover absorbs the death) — the
    number the soak acceptance turns into a measured claim."""
    import subprocess
    import tempfile
    import urllib.request

    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration, chaos)
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                   OutputLayer)
    from deeplearning4j_tpu.serving.fleet import ReplicaFleet
    from deeplearning4j_tpu.serving.router import Router
    from deeplearning4j_tpu.util.model_serializer import write_model

    feat, hidden, classes, max_bs = 32, 128, 16, 32
    conf = (NeuralNetConfiguration.builder().set_seed(0)
            .updater(updaters.adam(1e-3)).list()
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_out=classes, loss="mcxent"))
            .set_input_type(InputType.feed_forward(feat)).build())
    tmp = tempfile.mkdtemp(prefix="bench_fleet_")
    model_zip = os.path.join(tmp, "mlp.zip")
    write_model(MultiLayerNetwork(conf).init(), model_zip)

    def loadgen(router_port, total, retries=3):
        # loadgen runs OUT of process: client threads inside this
        # process would share the router's GIL and measure their
        # own contention, not the fleet's throughput
        proc = subprocess.run(
            [sys.executable, "-m", "tools.loadgen",
             "--url", f"http://127.0.0.1:{router_port}",
             "--features", str(feat),
             "--concurrency", str(ROUTER_CONC),
             "--total", str(total),
             "--timeout", "30", "--retries", str(retries)],
            capture_output=True, text=True, timeout=300,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        if not proc.stdout.strip():
            # a crashed loadgen child must surface its own
            # diagnostic, not an opaque JSONDecodeError on ''.
            # NOTE: exit 1 with a report on stdout just means
            # failed>0 — that report is the measurement (the SIGKILL
            # leg asserts on its failed/errors fields), never raise
            raise RuntimeError(
                f"loadgen exited {proc.returncode} with no report; "
                f"stderr: {proc.stderr[-800:]}")
        return json.loads(proc.stdout)

    def run(n, base_port, kill_at=None):
        fleet = ReplicaFleet(
            model_specs=[f"default={model_zip}"], n=n,
            base_port=base_port).start()
        router = Router(fleet, probe_interval_s=0.25,
                        hedge_after_s=None, sample_rate=0.0).start()
        try:
            # readiness gate: subprocess replicas import jax and
            # restore the model before they listen — wait until the
            # router's prober sees every replica up
            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{router.port}"
                            "/healthz", timeout=5.0) as r:
                        if json.load(r).get("eligible") == n:
                            break
                except OSError:
                    pass
                time.sleep(0.25)
            else:
                raise RuntimeError(
                    f"fleet of {n} never became ready")
            # warmup OUTSIDE the measured window: first requests
            # compile each pow2 batch shape on every replica
            loadgen(router.port, 8 * ROUTER_CONC * n)
            if kill_at is not None:
                chaos.install({"faults": [
                    {"site": "serving.replica", "kind": "kill",
                     "at": [kill_at], "args": {"replica": 0}}]},
                    seed=1234)
            rep = loadgen(router.port, ROUTER_REQUESTS)
        finally:
            chaos.uninstall()
            router.stop()
            fleet.stop(drain=False, timeout=5.0)
        return rep

    r1 = run(1, 18310)
    r4 = run(4, 18320)
    rk = run(4, 18330, kill_at=ROUTER_REQUESTS // 3)
    if rk["failed"] or r4["failed"] or r1["failed"]:
        raise RuntimeError(
            f"router_fleet dropped requests: n1={r1['failed']} "
            f"n4={r4['failed']} kill={rk['failed']} "
            f"({rk['errors']})")
    print(f"router_fleet: N=1 {r1['achieved_qps']:.0f} q/s p99 "
          f"{r1['latency_ms']['p99']:.1f} ms; N=4 "
          f"{r4['achieved_qps']:.0f} q/s p99 "
          f"{r4['latency_ms']['p99']:.1f} ms; N=4+SIGKILL "
          f"{rk['achieved_qps']:.0f} q/s p99 "
          f"{rk['latency_ms']['p99']:.1f} ms, 0 dropped",
          file=sys.stderr)
    return {
        "metric": (f"serving fleet sustained QPS through the "
                   f"router (closed loop, {ROUTER_CONC} clients, "
                   f"1-row MLP predicts, N=4 subprocess replicas)"),
        "value": r4["achieved_qps"], "unit": "requests/sec",
        "baseline": r1["achieved_qps"],
        "vs_baseline": round(r4["achieved_qps"]
                             / max(r1["achieved_qps"], 1e-9), 3),
        "p99_n1_ms": r1["latency_ms"]["p99"],
        "p99_n4_ms": r4["latency_ms"]["p99"],
        "p99_n4_sigkill_ms": rk["latency_ms"]["p99"],
        "qps_n4_sigkill": rk["achieved_qps"],
        "sigkill_dropped": rk["failed"],
        "sigkill_retries": rk["retries"],
        "host_cpus": os.cpu_count(),
        "mfu": None,
        "note": ("value: N=4 subprocess-replica fleet behind "
                 "serving/router.py (health probes, least-loaded "
                 "balancing, failover; hedging off); baseline: the "
                 "same router over N=1. The SIGKILL row reruns N=4 "
                 "with a seeded serving.replica chaos kill (a real "
                 "signal 9 to the child) at request ordinal "
                 f"{ROUTER_REQUESTS // 3}: zero dropped requests — "
                 "failover absorbs the death, the tail pays for "
                 "it. Replicas are separate processes on loopback "
                 "HTTP, one physical host — QPS measures the "
                 "router+fleet stack, not multi-host scale-out")}


OBS_OVERHEAD_BAR = 0.02   # ≤2% QPS cost with 1 s collector scrapes
# per measured run: ~2.4k requests ≈ 7 s at this host's QPS, so each
# window samples several whole scrape cycles — 600-request windows
# are shorter than the scrape interval and measure boundary luck
OBS_REQUESTS = 2400


def _leg_observability_overhead(peak):
    """What the fleet observability plane costs the serving path: the
    router_fleet harness (N=2 subprocess replicas, out-of-process
    loadgen) re-run with a FleetCollector scraping every member's
    /metrics + /debug/trace-export at a 1 s interval, vs collector
    off. The collector is pull-based and out of the request path, so
    the cost is bounded by the /metrics render under load.
    Bar: ≤2% QPS cost."""
    import statistics
    import subprocess
    import tempfile
    import urllib.request

    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                   OutputLayer)
    from deeplearning4j_tpu.observability.fleetobs import (
        FleetCollector)
    from deeplearning4j_tpu.serving.fleet import ReplicaFleet
    from deeplearning4j_tpu.serving.router import Router
    from deeplearning4j_tpu.util.model_serializer import write_model

    feat, hidden, classes = 32, 128, 16
    conf = (NeuralNetConfiguration.builder().set_seed(0)
            .updater(updaters.adam(1e-3)).list()
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_out=classes, loss="mcxent"))
            .set_input_type(InputType.feed_forward(feat)).build())
    tmp = tempfile.mkdtemp(prefix="bench_obs_")
    model_zip = os.path.join(tmp, "mlp.zip")
    write_model(MultiLayerNetwork(conf).init(), model_zip)

    def loadgen(router_port, total):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.loadgen",
             "--url", f"http://127.0.0.1:{router_port}",
             "--features", str(feat),
             "--concurrency", str(ROUTER_CONC),
             "--total", str(total),
             "--timeout", "30", "--retries", "3"],
            capture_output=True, text=True, timeout=300,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        if not proc.stdout.strip():
            raise RuntimeError(
                f"loadgen exited {proc.returncode} with no report; "
                f"stderr: {proc.stderr[-800:]}")
        return json.loads(proc.stdout)

    n = 2
    fleet = ReplicaFleet(model_specs=[f"default={model_zip}"], n=n,
                         base_port=18350).start()
    router = Router(fleet, probe_interval_s=0.25,
                    hedge_after_s=None, sample_rate=0.01).start()
    try:
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{router.port}/healthz",
                        timeout=5.0) as r:
                    if json.load(r).get("eligible") == n:
                        break
            except OSError:
                pass
            time.sleep(0.25)
        else:
            raise RuntimeError(f"fleet of {n} never became ready")
        loadgen(router.port, 8 * ROUTER_CONC * n)    # warmup/compile

        def run_with_collector():
            col = FleetCollector(fleet=fleet, router=router,
                                 interval_s=1.0, port=0).start()
            router.attach_fleet_health(col.fleet_health)
            try:
                return loadgen(router.port, OBS_REQUESTS)
            finally:
                router.attach_fleet_health(None)
                col.stop()

        def run_without():
            return loadgen(router.port, OBS_REQUESTS)

        # PAIRED back-to-back ratios, median over alternating-order
        # pairs — the same drift-robust shape as tracing_overhead
        ratios, qps_off, qps_on, dropped = [], [], [], 0
        for i in range(4):
            if i % 2 == 0:
                off, on = run_without(), run_with_collector()
            else:
                on, off = run_with_collector(), run_without()
            for rep in (off, on):
                dropped += rep["failed"]
            qps_off.append(off["achieved_qps"])
            qps_on.append(on["achieved_qps"])
            ratios.append(on["achieved_qps"]
                          / max(off["achieved_qps"], 1e-9))
        rel = statistics.median(ratios)
    finally:
        router.stop()
        fleet.stop(drain=False, timeout=5.0)
    if dropped:
        raise RuntimeError(
            f"observability_overhead dropped {dropped} requests")
    overhead = max(0.0, 1.0 - rel)
    print(f"observability overhead: scraped "
          f"{statistics.median(qps_on):.0f} q/s vs unscraped "
          f"{statistics.median(qps_off):.0f} q/s → {rel:.3f}x "
          f"({overhead * 100:.1f}% cost)", file=sys.stderr)
    return {
        "metric": (f"fleet-collector scrape overhead (router over "
                   f"N={n} subprocess replicas, {ROUTER_CONC} "
                   "closed-loop clients, 1 s scrape interval)"),
        "value": round(rel, 3),
        "unit": "throughput ratio (collector on / off)",
        "baseline": 1.0,
        "vs_baseline": round(rel, 3),
        "qps_collector_on": round(statistics.median(qps_on), 1),
        "qps_collector_off": round(statistics.median(qps_off), 1),
        "overhead": round(overhead, 4),
        "bar_overhead": OBS_OVERHEAD_BAR,
        "passed_bar": bool(overhead <= OBS_OVERHEAD_BAR),
        "host_cpus": os.cpu_count(),
        "mfu": None,
        "note": ("router_fleet harness with observability/"
                 "fleetobs.py FleetCollector scraping every "
                 "member's /metrics (OpenMetrics) and draining "
                 "/debug/trace-export each second, SLO evaluation "
                 "and fleet /healthz feedback attached, vs the "
                 "identical fleet unscraped. Median of 4 paired "
                 "back-to-back ratios, pair order alternating; "
                 "bar: ≤2% QPS cost — the collector is pull-based "
                 "and off the request path")}


def _leg_autoscaler_soak(peak):
    """The self-healing-fleet drill as a measured claim: a ~6x QPS
    step over a 1-replica fleet with a seeded whole-replica kill
    mid-spike, tiered traffic (gold/standard/best_effort). Headline:
    seconds from SLO breach to SLO recovery with the autoscaler
    closing the loop (bounds 1..3), vs the same spike on a FIXED
    1-replica fleet (no autoscaler, no kill) where the SLO only
    recovers when the spike ends. Also records per-tier outcomes:
    zero gold-tier drops, best-effort shed first.

    Replica capacity is an explicit per-request service time (a
    sleep-based model), NOT device compute: on this 2-core host the
    router stack itself is host-bound at ~50 q/s (see router_fleet),
    so real-model replicas could not show capacity scaling. The leg
    measures the CONTROL LOOP — detection, boot-first scale-up,
    recovery — and the admission tiering, with loadgen in-process."""
    import threading as _th

    from deeplearning4j_tpu import chaos
    from deeplearning4j_tpu.observability.slo import (BurnWindow, SLO,
                                                      SLOMonitor)
    from deeplearning4j_tpu.serving.autoscaler import Autoscaler
    from deeplearning4j_tpu.serving.fleet import ReplicaFleet
    from deeplearning4j_tpu.serving.router import Router
    from tools.loadgen import (LoadGen, parse_profile,
                               parse_tier_mix, tiered_body_fn)

    class DelayModel:
        def __init__(self, delay_s):
            self.delay_s = delay_s

        def output(self, x):
            time.sleep(self.delay_s)
            return np.asarray(x)

    MIX = "gold=0.2,standard=0.5,best_effort=0.3"
    PROFILE = "step:8:48:2"
    DURATION = 14.0

    def run(autoscale, kill_at=None):
        fleet = ReplicaFleet(
            lambda: {"default": DelayModel(0.04)}, n=1,
            server_kwargs=dict(wait_ms=1.0, max_batch_size=1,
                               queue_limit=6)).start()
        router = Router(fleet, probe_interval_s=0.1,
                        probe_timeout_s=0.5, attempt_timeout_s=3.0,
                        request_timeout_s=8.0, hedge_after_s=None,
                        sample_rate=0.0).start()
        slos = SLOMonitor(router.registry, [SLO(
            name="router_p_latency", objective=0.8, threshold_s=0.1,
            metric="router_latency_seconds",
            labels={"route": "/v1/predict"}, window_s=30.0,
            windows=[BurnWindow(short_s=1.5, long_s=4.0,
                                factor=1.5)])],
            min_eval_interval_s=0.2)
        scaler = None
        if autoscale:
            scaler = Autoscaler(
                fleet, router, slos=slos, registry=router.registry,
                min_replicas=1, max_replicas=3,
                tick_interval_s=0.25, queue_high=3.0,
                queue_low=0.25, up_consecutive=2,
                down_consecutive=10_000, up_cooldown_s=1.5,
                down_cooldown_s=60.0).start()
        if kill_at is not None:
            chaos.install({"faults": [
                {"site": "serving.replica", "kind": "kill",
                 "at": [kill_at], "args": {"replica": 0}}]},
                seed=99)
        body = tiered_body_fn(
            lambda i: {"model": "default",
                       "inputs": [[float(i % 7), 1.0]]},
            parse_tier_mix(MIX))
        gen = LoadGen(f"http://127.0.0.1:{router.port}",
                      body_fn=body, concurrency=24,
                      profile=parse_profile(PROFILE),
                      duration_s=DURATION, timeout_s=6.0,
                      max_retries=6, backlog_limit=512)
        marks = {"breach": None, "recover": None}
        t0 = time.monotonic()
        out = {}

        def load():
            out["report"] = gen.run()

        lt = _th.Thread(target=load, daemon=True)
        lt.start()
        try:
            deadline = t0 + DURATION + 30.0
            while time.monotonic() < deadline:
                b = slos.any_breached()
                now = time.monotonic() - t0
                if b and marks["breach"] is None:
                    marks["breach"] = now
                if not b and marks["breach"] is not None:
                    marks["recover"] = now
                    break
                time.sleep(0.1)
            lt.join(timeout=30.0)
            final_replicas = fleet.size()
        finally:
            chaos.uninstall()
            if scaler is not None:
                scaler.stop(wait_retires=False)
            router.stop()
            fleet.stop(drain=False, timeout=2.0)
        rep = out.get("report", {})
        ups = router.registry.get(
            "autoscaler_scale_events_total",
            labels={"direction": "up"})
        return {"breach_s": marks["breach"],
                "recover_s": marks["recover"],
                "recovery_s": (None if None in marks.values()
                               else round(marks["recover"]
                                          - marks["breach"], 2)),
                "scale_ups": 0 if ups is None else int(ups.value),
                "final_replicas": final_replicas,
                "tiers": rep.get("tiers", {}),
                "failed": rep.get("failed"), "ok": rep.get("ok")}

    scaled = run(autoscale=True, kill_at=150)
    fixed = run(autoscale=False)
    if scaled["recovery_s"] is None:
        raise RuntimeError(
            f"autoscaled run never breached+recovered: {scaled}")
    gold = scaled["tiers"].get("gold", {})
    if gold.get("failed", 1) != 0:
        raise RuntimeError(
            f"gold-tier drops under the autoscaled drill: {gold}")
    fixed_rec = fixed["recovery_s"]
    print(f"autoscaler_soak: breach @{scaled['breach_s']:.1f}s, "
          f"recovered in {scaled['recovery_s']:.1f}s "
          f"({scaled['scale_ups']} scale-ups, kill absorbed, gold "
          f"0 dropped); fixed fleet recovery "
          f"{fixed_rec if fixed_rec is not None else '>30'}s",
          file=sys.stderr)
    return {
        "metric": ("autoscaler SLO-recovery time: ~6x QPS step + "
                   "replica SIGKILL mid-spike, fleet bounds 1..3 "
                   "(in-process replicas, 40ms service time, "
                   "tiered load)"),
        "value": scaled["recovery_s"], "unit": "seconds",
        "baseline": fixed_rec,
        "vs_baseline": (None if not fixed_rec else round(
            fixed_rec / scaled["recovery_s"], 3)),
        "scale_ups": scaled["scale_ups"],
        "final_replicas": scaled["final_replicas"],
        "gold_outcomes": scaled["tiers"].get("gold"),
        "standard_outcomes": scaled["tiers"].get("standard"),
        "best_effort_outcomes": scaled["tiers"].get("best_effort"),
        "fixed_fleet_tiers": fixed["tiers"],
        "host_cpus": os.cpu_count(),
        "mfu": None,
        "note": ("value: breach->recovery seconds with the "
                 "autoscaler closing the loop (step:8:48:2 q/s at "
                 "t=2s, seeded serving.replica kill at request "
                 "ordinal 150 mid-spike; SLO = 80% of "
                 "/v1/predict under 100ms, 1.5s/4s burn windows). "
                 "baseline: the same step on a FIXED 1-replica "
                 "fleet (no kill) — it exits breach too, but only "
                 "by mass-shedding (fast 429s dilute the latency "
                 "objective): see fixed_fleet_tiers — dozens of "
                 "standard/best_effort requests dropped outright "
                 "and even gold pays sheds+retries, vs zero gold "
                 "and zero standard drops with the autoscaler. "
                 "Replicas are sleep-based 40ms-service-time "
                 "models behind real ModelServer/Router HTTP: the "
                 "2-core host is router-bound (router_fleet), so "
                 "the leg measures the control loop + tier "
                 "admission, not hardware scale-out. The drill "
                 "requires ZERO gold failures")}


def _leg_rollout_soak(peak):
    """The canary-rollout drill as a measured claim, both directions:
    a GOOD candidate (behavior-equivalent retrain) promoted
    fleet-wide through the SLO gate, and a BAD candidate
    (NaN-poisoned via a seeded `serving.rollout` `bad_version`
    fault) detected by shadow scoring and automatically rolled
    back. 4 in-process replicas behind the real Router/collector
    stack under live gold/standard/best_effort load. Headlines:
    good-canary time-to-promoted and bad-canary
    time-to-detected-and-rolled-back (status `started_unix` →
    `finished_unix`), with ZERO gold drops in both runs, capacity
    never below 4, and exactly one incident bundle from the bad
    run. Like autoscaler_soak this measures the CONTROL LOOP, not
    device compute."""
    import json as _json
    import shutil
    import tempfile
    import threading as _th
    import urllib.request

    from deeplearning4j_tpu import chaos
    from deeplearning4j_tpu.observability.fleetobs import \
        FleetCollector
    from deeplearning4j_tpu.serving.fleet import UP, ReplicaFleet
    from deeplearning4j_tpu.serving.router import Router
    from deeplearning4j_tpu.serving.rollout import RolloutController

    class EchoModel:
        def output(self, x):
            return np.asarray(x, dtype=np.float32) * 2.0

    TIERS = ("gold", "standard", "best_effort")

    def post(base, body):
        req = urllib.request.Request(
            base + "/v1/predict",
            data=_json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=10.0) as r:
                return r.status, _json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, {}
        except Exception:
            return 0, {}

    def run(bad, inc_dir, seed=23):
        fleet = ReplicaFleet(
            lambda: {"default": EchoModel()}, n=4,
            server_kwargs=dict(wait_ms=1.0, max_batch_size=8,
                               queue_limit=64)).start()
        router = Router(fleet, probe_interval_s=0.05,
                        probe_timeout_s=0.5, attempt_timeout_s=2.0,
                        request_timeout_s=10.0, hedge_after_s=None,
                        sample_rate=1.0).start()
        col = FleetCollector(fleet=fleet, router=router,
                             interval_s=0.25,
                             incident_min_interval_s=0.0,
                             incident_dir=inc_dir).start()
        rc = RolloutController(
            fleet, router,
            candidate_factory=lambda: {"default": EchoModel()},
            collector=col, canary_weight=0.25, shadow_sample=0.5,
            min_requests=40, warmup_requests=10,
            min_shadow_compared=10, gate_poll_s=0.1,
            # wide open: on this 1-2 core host a freshly-booted
            # canary's scheduling jitter can trip any tight ratio —
            # the leg times the control loop; the bad candidate is
            # caught by shadow scoring, which is load-independent
            drain_timeout_s=5.0, max_p99_ratio=50.0)
        if bad:
            chaos.install({"faults": [
                {"site": "serving.rollout", "kind": "bad_version",
                 "at": [1]}]}, seed=seed)
        base = f"http://127.0.0.1:{router.port}"
        counts = {t: {"ok": 0, "dropped": 0} for t in TIERS}
        stop = _th.Event()
        mincap = [10**9]

        def drive(tier):
            i = 0
            while not stop.is_set():
                i += 1
                st, _b = post(base, {"model": "default",
                                     "inputs": [[float(i % 5)]],
                                     "tier": tier})
                counts[tier]["ok" if st == 200
                             else "dropped"] += 1
                mincap[0] = min(mincap[0], sum(
                    1 for r in fleet.snapshot()
                    if r.fleet_state == UP))
                time.sleep(0.004)

        threads = [_th.Thread(target=drive, args=(t,), daemon=True)
                   for t in TIERS]
        out = {}

        def roll():
            out["status"] = rc.run()

        rt = _th.Thread(target=roll, daemon=True)
        try:
            for t in threads:
                t.start()
            time.sleep(1.0)       # incumbent evidence before start
            rt.start()
            rt.join(timeout=120.0)
            if rt.is_alive():
                rc.abort("bench watchdog")
                rt.join(timeout=30.0)
            time.sleep(0.5)       # let in-flight drain into counts
            versions = sorted(fleet.versions().values())
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10.0)
            chaos.uninstall()
            col.stop()
            router.stop()
            fleet.stop(drain=False, timeout=5.0)
        st = out.get("status") or {}
        elapsed = (None if not st.get("finished_unix")
                   else round(st["finished_unix"]
                              - st["started_unix"], 2))
        incidents = sorted(
            d for d in os.listdir(inc_dir)
            if d.startswith("incident-"))
        return {"status": st, "elapsed_s": elapsed,
                "tiers": counts, "min_capacity": mincap[0],
                "versions": versions, "incidents": incidents}

    tmp_good = tempfile.mkdtemp(prefix="bench-rollout-good-")
    tmp_bad = tempfile.mkdtemp(prefix="bench-rollout-bad-")
    try:
        good = run(bad=False, inc_dir=tmp_good)
        bad = run(bad=True, inc_dir=tmp_bad)
        for name, r in (("good", good), ("bad", bad)):
            if r["tiers"]["gold"]["dropped"] != 0:
                raise RuntimeError(
                    f"gold drops in the {name} rollout: {r}")
            if r["min_capacity"] < 4:
                raise RuntimeError(
                    f"capacity dipped below N in {name}: {r}")
        if good["status"].get("outcome") != "promoted":
            raise RuntimeError(f"good canary not promoted: {good}")
        if set(good["versions"]) != {2}:
            raise RuntimeError(
                f"good rollout left mixed versions: {good}")
        if bad["status"].get("outcome") != "rolled_back":
            raise RuntimeError(f"bad canary not rolled back: {bad}")
        if set(bad["versions"]) != {1}:
            raise RuntimeError(
                f"bad rollout left candidate replicas: {bad}")
        if len(bad["incidents"]) != 1:
            raise RuntimeError(
                f"expected exactly one incident: {bad['incidents']}")
        gate = bad["status"].get("last_gate")
    finally:
        shutil.rmtree(tmp_good, ignore_errors=True)
        shutil.rmtree(tmp_bad, ignore_errors=True)
    print(f"rollout_soak: good canary promoted fleet-wide in "
          f"{good['elapsed_s']}s; bad canary caught by gate "
          f"'{gate}' and rolled back in {bad['elapsed_s']}s "
          f"(one incident, zero gold drops both runs)",
          file=sys.stderr)
    return {
        "metric": ("canary rollout control loop: bad-candidate "
                   "(seeded serving.rollout bad_version NaN "
                   "poison) detect->rollback time, 4 in-process "
                   "replicas under tiered load"),
        "value": bad["elapsed_s"], "unit": "seconds",
        "good_promotion_s": good["elapsed_s"],
        "bad_gate": gate,
        "good_gold_outcomes": good["tiers"]["gold"],
        "bad_gold_outcomes": bad["tiers"]["gold"],
        "good_holds": good["status"].get("holds"),
        "incidents": len(bad["incidents"]),
        "host_cpus": os.cpu_count(),
        "mfu": None,
        "note": ("value: start->rolled-back seconds for a "
                 "candidate whose outputs are NaN-poisoned by the "
                 "seeded serving.rollout fault — caught by shadow "
                 "scoring (gate in bad_gate), auto-rolled-back to "
                 "4/4 incumbent with exactly one incident bundle. "
                 "good_promotion_s: start->promoted seconds for a "
                 "behavior-equivalent candidate through the full "
                 "canary->expanding ladder (comparative windowed "
                 "SLO gate against the incumbent cohort). Both "
                 "runs under live gold/standard/best_effort load: "
                 "ZERO gold drops required, UP capacity never "
                 "below 4 (boot-successor-first replaces). "
                 "Like autoscaler_soak, this measures the control "
                 "loop on loopback HTTP, not device compute")}


DECODE_STEPS = 128
DECODE_CAP = 256
MASKED_ATTN_SHAPE = (4, 4096, 8, 64)     # B, T, H, D
MASKED_ATTN_BURST = 100                  # chained steps per burst


def _leg_transformer_decode(peak):
    """Streaming decode for the transformer-LM config: the jitted
    fixed-capacity KV-cache session (models/streaming.py) vs the
    eager concat-cache rnn_time_step path — same contract (parity
    tested in tests/), one XLA dispatch per token vs a Python op
    stream, O(t) vs O(pos) cache traffic per step (round-4 verdict
    weak #7)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration, dtypes)
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (
        EmbeddingSequenceLayer, RnnOutputLayer, TransformerEncoderLayer)

    b = (NeuralNetConfiguration.builder().set_seed(0)
         .updater(updaters.adam(1e-3)).list()
         .layer(EmbeddingSequenceLayer(n_in=LM_V, n_out=LM_D)))
    for _ in range(LM_L):
        b = b.layer(TransformerEncoderLayer(n_heads=LM_H, causal=True))
    conf = (b.layer(RnnOutputLayer(n_out=LM_V, loss="mcxent"))
            .set_input_type(InputType.recurrent(LM_V, DECODE_CAP))
            .build())
    with dtypes.policy_scope(dtypes.tpu_bf16()):
        net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    # fixed id stream (not sampled from the model): keeps every step
    # device-side with no per-token host sync; the cache carry is the
    # cross-step data dependency the final fetch rides on
    ids = rng.integers(0, LM_V, (DECODE_STEPS, LM_B, 1)).astype(
        "float32")

    sess = net.streaming_session(capacity=DECODE_CAP, batch=LM_B,
                                 dtype=jnp.bfloat16)
    h = sess.step(ids[0])               # compile the t=1 executable
    float(jnp.sum(h))

    bounded_ctr = [0]

    def m_bounded():
        # drift the id stream per burst, so no two bursts replay
        # byte-identical calls — same discipline as the fused window
        # below
        bounded_ctr[0] += 1
        ids_b = (ids + bounded_ctr[0]) % LM_V
        sess.reset()
        t0 = time.perf_counter()
        for s in range(DECODE_STEPS):
            h = sess.step(ids_b[s])
        float(jnp.sum(h))               # host fetch = end-of-burst sync
        return time.perf_counter() - t0

    # few eager steps: each token-step is DOZENS of un-jitted op
    # dispatches — the baseline only needs enough steps for a stable
    # per-token rate, and the short history already flatters it
    eager_steps = 6
    net.rnn_clear_previous_state()
    h = net.rnn_time_step(ids[0])       # warm the eager op caches
    float(jnp.sum(h))

    def m_eager():
        net.rnn_clear_previous_state()
        t0 = time.perf_counter()
        for s in range(eager_steps):
            h = net.rnn_time_step(ids[s])
        float(jnp.sum(h))
        return time.perf_counter() - t0

    dt_b, dt_e = _interleave(m_bounded, m_eager, repeats=3)
    rate_b = DECODE_STEPS * LM_B / dt_b
    rate_e = eager_steps * LM_B / dt_e

    # FUSED decode: the whole generation is ONE lax.scan program —
    # a single dispatch replaces DECODE_STEPS of them (greedy
    # sampling included), which is where the dispatch-bound decode
    # regime actually wants to live. The prompt CONTENT changes per
    # burst (a constant prompt with deterministic greedy decode would
    # repeat byte-identical calls), and the fused/bounded ratio comes
    # from alternating bursts within ONE window.
    fused_ctr = [0]
    sess.reset()
    gen_ids = sess.generate(np.zeros((LM_B, 1), np.float32),
                            DECODE_STEPS, fused=True)   # compile
    float(jnp.sum(gen_ids))

    def m_fused():
        fused_ctr[0] += 1
        prompt = np.full((LM_B, 1), fused_ctr[0] % LM_V, np.float32)
        sess.reset()
        t0 = time.perf_counter()
        out = sess.generate(prompt, DECODE_STEPS, fused=True)
        float(jnp.sum(out))
        return time.perf_counter() - t0

    dt_b2, dt_f = _interleave(m_bounded, m_fused, repeats=3)
    rate_f = DECODE_STEPS * LM_B / dt_f
    fused_vs_bounded = dt_b2 / dt_f
    print(f"transformer decode: bounded-cache {rate_b:.0f} tok/s, "
          f"eager rnn_time_step {rate_e:.0f} tok/s "
          f"({rate_b / rate_e:.1f}x); FUSED scan generate "
          f"{rate_f:.0f} tok/s ({fused_vs_bounded:.1f}x bounded)",
          file=sys.stderr)
    return {
        "metric": (f"Transformer-LM streaming decode (B={LM_B}, "
                   f"d={LM_D}, L={LM_L}, heads={LM_H}, vocab {LM_V}, "
                   f"cap {DECODE_CAP}, bf16 cache)"),
        "value": round(rate_b, 0), "unit": "tokens/sec/chip",
        "baseline": round(rate_e, 0),
        "vs_baseline": round(rate_b / rate_e, 3),
        "fused_scan_tokens_per_sec": round(rate_f, 0),
        "fused_vs_bounded": round(fused_vs_bounded, 3),
        "mfu": None,
        "note": (f"value: jitted fixed-capacity KV-cache session, "
                 f"{DECODE_STEPS} single-token steps; baseline: "
                 f"eager concat-cache rnn_time_step over its FIRST "
                 f"{eager_steps} tokens (short history flatters it — "
                 f"its per-step cost grows with position); "
                 f"fused_scan = generate(fused=True): the whole "
                 f"{DECODE_STEPS}-token greedy decode as ONE XLA "
                 f"program (single dispatch). Parity of all paths "
                 f"is asserted in tests/test_native_and_kernels.py")}


PAGED_V, PAGED_D, PAGED_L, PAGED_H = 256, 128, 2, 4
PAGED_SLOTS = 8
PAGED_CAP = 160
PAGED_PS = 16                 # tokens per KV page
PAGED_POOL = 20               # fixed-memory pool for the slot-count leg
PAGED_STEPS = 96
PAGED_PROMPT = 64
SPEC_K = 8
SPEC_TOKENS = 96


def _paged_lm(seed, width, layers, heads):
    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (
        EmbeddingSequenceLayer, RnnOutputLayer, TransformerEncoderLayer)
    b = (NeuralNetConfiguration.builder().set_seed(seed)
         .updater(updaters.adam(1e-3)).list()
         .layer(EmbeddingSequenceLayer(n_in=PAGED_V, n_out=width)))
    for _ in range(layers):
        b = b.layer(TransformerEncoderLayer(n_heads=heads,
                                            causal=True))
    conf = (b.layer(RnnOutputLayer(n_out=PAGED_V, loss="mcxent"))
            .set_input_type(InputType.recurrent(PAGED_V, PAGED_CAP))
            .build())
    return MultiLayerNetwork(conf).init()


def _leg_transformer_decode_paged(peak):
    """The decode fast path end to end: (a) paged-KV slot decode vs
    the dense per-slot session at batch N (same math, page-table
    gather — greedy parity is tested in tests/test_decode_paged.py),
    (b) prefix-cache TTFT on a repeated prompt vs cold prefill
    through ContinuousBatcher, (c) draft-model speculative decode vs
    vanilla greedy, and (d) the memory story: concurrent slots at a
    FIXED KV budget, paged vs the dense bucket limit."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.speculative import (
        SpeculativeDecoder)
    from deeplearning4j_tpu.serving.continuous import ContinuousBatcher

    net = _paged_lm(0, PAGED_D, PAGED_L, PAGED_H)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, PAGED_V,
                       (PAGED_STEPS, PAGED_SLOTS, 1, 1)).astype(
                           np.float32)
    active = np.ones((PAGED_SLOTS,), bool)

    # ---- (a) dense vs paged slot-step decode at batch N ----
    dense = net.slot_streaming_session(capacity=PAGED_CAP,
                                       slots=PAGED_SLOTS)
    paged = net.paged_slot_streaming_session(
        capacity=PAGED_CAP, slots=PAGED_SLOTS, page_size=PAGED_PS)

    def _bind_all(sess):
        for s in range(PAGED_SLOTS):
            sess.bind(s, sess.reserve([1], PAGED_STEPS + 2))

    _bind_all(paged)
    float(jnp.sum(dense.step_slots(ids[0], active)))   # compile
    float(jnp.sum(paged.step_slots(ids[0], active)))
    drift = [0]

    def _measure(sess, is_paged):
        def m():
            # drift the id stream per burst (same discipline as
            # transformer_decode)
            drift[0] += 1
            ids_b = (ids + drift[0]) % PAGED_V
            if is_paged:
                sess.release_all()
                _bind_all(sess)
            else:
                sess.reset()
            t0 = time.perf_counter()
            for s in range(PAGED_STEPS):
                h = sess.step_slots(ids_b[s], active)
            float(jnp.sum(h))
            return time.perf_counter() - t0
        return m

    dt_p, dt_d = _interleave(_measure(paged, True),
                             _measure(dense, False), repeats=3)
    rate_p = PAGED_STEPS * PAGED_SLOTS / dt_p
    rate_d = PAGED_STEPS * PAGED_SLOTS / dt_d

    # ---- (b) prefix-cache TTFT through the batcher ----
    cb = ContinuousBatcher(net, slots=4, capacity=PAGED_CAP,
                           kv_mode="paged", page_size=PAGED_PS,
                           name="bench_paged")
    try:
        warm = rng.integers(1, PAGED_V, (PAGED_PROMPT,))
        cb.generate(warm, 1)               # compile + worker warmup
        prompt = rng.integers(1, PAGED_V, (PAGED_PROMPT,))
        t0 = time.perf_counter()
        cb.generate(prompt, 1)
        ttft_cold = time.perf_counter() - t0
        ttft_hit = float("inf")
        for _ in range(3):                 # prefix registered at
            t0 = time.perf_counter()       # first completion
            cb.generate(prompt, 1)
            ttft_hit = min(ttft_hit, time.perf_counter() - t0)
        prefix_hits = cb.session.prefix_cache.hits_total
    finally:
        cb.shutdown(drain=False)

    # ---- (c) speculative decode vs vanilla greedy ----
    draft = _paged_lm(7, 32, 1, 2)
    spec_tiny = SpeculativeDecoder(net, draft, k=SPEC_K,
                                   capacity=PAGED_CAP)
    spec_self = SpeculativeDecoder(net, net, k=SPEC_K,
                                   capacity=PAGED_CAP)
    vanilla = net.streaming_session(capacity=PAGED_CAP, batch=1)
    sp = rng.integers(1, PAGED_V, (1, 8))
    spec_tiny.generate(sp, SPEC_TOKENS)    # compile
    spec_self.generate(sp, SPEC_TOKENS)
    vanilla.reset()
    vanilla.generate(sp.astype(np.float32), SPEC_TOKENS)
    sctr = [0]

    def _m_spec(dec):
        def m():
            sctr[0] += 1
            p = (sp + sctr[0]) % PAGED_V
            t0 = time.perf_counter()
            dec.generate(p, SPEC_TOKENS)
            return time.perf_counter() - t0
        return m

    def _m_vanilla():
        sctr[0] += 1
        p = ((sp + sctr[0]) % PAGED_V).astype(np.float32)
        vanilla.reset()
        t0 = time.perf_counter()
        out = vanilla.generate(p, SPEC_TOKENS)
        float(jnp.sum(out))
        return time.perf_counter() - t0

    dt_self, dt_v = _interleave(_m_spec(spec_self), _m_vanilla,
                                repeats=3)
    dt_tiny, dt_v2 = _interleave(_m_spec(spec_tiny), _m_vanilla,
                                 repeats=3)
    dt_v = min(dt_v, dt_v2)
    rate_spec_self = SPEC_TOKENS / dt_self
    rate_spec_tiny = SPEC_TOKENS / dt_tiny
    rate_vanilla = SPEC_TOKENS / dt_v

    # ---- (d) concurrent slots at a FIXED KV budget ----
    pool_tokens = PAGED_POOL * PAGED_PS
    dense_slot_limit = pool_tokens // PAGED_CAP
    fixed = net.paged_slot_streaming_session(
        capacity=PAGED_CAP, slots=PAGED_SLOTS, page_size=PAGED_PS,
        n_pages=PAGED_POOL)
    from deeplearning4j_tpu.serving.errors import (
        KVPagePoolExhaustedError)
    short = rng.integers(1, PAGED_V, (8,))
    concurrent = 0
    try:
        for s in range(PAGED_SLOTS):
            fixed.bind(s, fixed.reserve(short, 24))   # 2 pages each
            concurrent += 1
    except KVPagePoolExhaustedError:
        pass          # the pool is the bound being measured; any
        # other exception is a real bug and must fail the leg

    print(f"paged decode: paged {rate_p:.0f} tok/s vs dense "
          f"{rate_d:.0f} tok/s at B={PAGED_SLOTS}; TTFT cold "
          f"{ttft_cold * 1e3:.1f} ms vs prefix-hit "
          f"{ttft_hit * 1e3:.1f} ms ({prefix_hits} hits); spec "
          f"self-draft {rate_spec_self:.0f} tok/s / tiny-draft "
          f"{rate_spec_tiny:.0f} (acc "
          f"{spec_tiny.acceptance_rate:.2f}) vs vanilla "
          f"{rate_vanilla:.0f}; {concurrent} concurrent slots vs "
          f"dense limit {dense_slot_limit} at {pool_tokens} tokens "
          f"KV", file=sys.stderr)
    return {
        "metric": (f"transformer_decode_paged: paged-KV continuous "
                   f"decode (B={PAGED_SLOTS} slots, d={PAGED_D}, "
                   f"L={PAGED_L}, heads={PAGED_H}, vocab {PAGED_V}, "
                   f"cap {PAGED_CAP}, page {PAGED_PS})"),
        "value": round(rate_p, 0), "unit": "tokens/sec/chip",
        "baseline": round(rate_d, 0),
        "vs_baseline": round(rate_p / rate_d, 3),
        "ttft_cold_ms": round(ttft_cold * 1e3, 3),
        "ttft_prefix_hit_ms": round(ttft_hit * 1e3, 3),
        "prefix_ttft_speedup": round(ttft_cold / ttft_hit, 3),
        "prefix_cache_hits": prefix_hits,
        "spec_self_draft_tokens_per_sec": round(rate_spec_self, 0),
        "spec_tiny_draft_tokens_per_sec": round(rate_spec_tiny, 0),
        "spec_vanilla_tokens_per_sec": round(rate_vanilla, 0),
        "spec_self_vs_vanilla": round(rate_spec_self / rate_vanilla,
                                      3),
        "spec_tiny_vs_vanilla": round(rate_spec_tiny / rate_vanilla,
                                      3),
        "spec_tiny_acceptance": round(spec_tiny.acceptance_rate, 4),
        "spec_k": SPEC_K,
        "kv_pool_tokens_fixed_mem": pool_tokens,
        "dense_slot_limit_at_fixed_mem": dense_slot_limit,
        "paged_concurrent_slots_at_fixed_mem": concurrent,
        "mfu": None,
        "note": (f"value/baseline: tokens/sec over {PAGED_STEPS} "
                 f"single-token steps with all {PAGED_SLOTS} slots "
                 "active — paged gathers each slot's page table, "
                 "dense indexes a private capacity-row cache (greedy "
                 "tokens bit-identical; tested). TTFT: "
                 "ContinuousBatcher n_tokens=1 request wall time; "
                 f"the prefix-hit path resumes after "
                 f"{PAGED_PROMPT // PAGED_PS} cached pages instead "
                 f"of {PAGED_PROMPT} teacher-forced prefill steps. "
                 "Speculative: self-draft (acceptance 1.0) is the "
                 "machinery ceiling — 2 draft dispatches (feed + "
                 f"fused k={SPEC_K} scan) + 1 chunked verify per "
                 "round replace k single-token dispatches; the "
                 "tiny-draft row is an UNTRAINED draft, so its "
                 "acceptance (~1/vocab) makes it a slowdown — a "
                 "distilled draft lands between the two rows. "
                 "Slot-count row: at a fixed "
                 "pool of KV memory the dense session can host only "
                 "floor(mem/capacity) slots; paged binds pages per "
                 "request's actual need")}


def _leg_flash_attention_masked(peak):
    """Variable-length batch at T=4096 through the kv-mask-aware
    Pallas kernels (fwd+bwd) vs (a) exact masked attention — the
    fallback a maskless kernel forces — and (b) the unmasked kernel —
    the masking overhead. Records the COMPONENTS.md claim as an
    artifact (round-4 verdict weak #6)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.attention import (_exact_masked,
                                                  flash_attention)
    B, T, H, D = MASKED_ATTN_SHAPE
    rngk = jax.random.PRNGKey(0)
    q = jax.random.normal(rngk, (B, T, H, D), jnp.float32)
    # ragged real lengths (1/4 .. full): the shapes stay static, the
    # mask carries the raggedness — the TPU-native variable-length
    # contract
    lens = tuple(T * (i + 1) // B for i in range(B))
    mask = jnp.asarray(
        np.arange(T)[None, :] < np.asarray(lens)[:, None],
        jnp.float32)

    def mk(fn):
        # chain grad(q) into the next input, so the final fetch
        # depends on every step (see _leg_flash_attention)
        g = jax.jit(jax.grad(
            lambda x: jnp.sum((fn(x, x, x)
                               * mask[:, :, None, None]) ** 2)))
        float(jnp.sum(g(q)))
        burst = MASKED_ATTN_BURST

        def measure():
            a = q
            t0 = time.perf_counter()
            for _ in range(burst):
                a = g(a)
            float(jnp.sum(a))
            return (time.perf_counter() - t0) / burst
        return measure

    m_masked = mk(lambda a, b, c: flash_attention(a, b, c,
                                                  kv_mask=mask))
    m_exact = mk(lambda a, b, c: _exact_masked(a, b, c, mask, False))
    m_unmasked = mk(lambda a, b, c: flash_attention(a, b, c))
    # two interleave windows, both anchored on the masked kernel so
    # each ratio comes from alternating bursts within one window
    dt_m, dt_e = _interleave(m_masked, m_exact, repeats=3)
    dt_m2, dt_u = _interleave(m_masked, m_unmasked, repeats=3)
    toks = float(sum(lens))            # real (unpadded) tokens
    attn_flops = 14 * T * T * D * B * H
    if peak:
        _check_plausible(attn_flops / min(dt_m, dt_e) / peak,
                         "masked flash attention")
        _check_plausible(attn_flops / min(dt_m2, dt_u) / peak,
                         "masked flash (unmasked window)")
    print(f"masked flash T={T} ragged fwd+bwd: "
          f"{toks/dt_m:.0f} real tok/s; vs exact masked "
          f"{dt_e/dt_m:.2f}x; vs unmasked kernel "
          f"{dt_u/dt_m2:.3f}x", file=sys.stderr)
    return {
        "metric": ("masked flash attention fwd+bwd, ragged batch "
                   f"(B={B}, T={T}, lens={list(lens)}, H={H}, D={D}, "
                   "f32)"),
        "value": round(toks / dt_m, 0), "unit": "real tokens/sec",
        "baseline": round(toks / dt_e, 0),
        "vs_baseline": round(dt_e / dt_m, 3),
        "vs_exact_masked": round(dt_e / dt_m, 3),
        "vs_unmasked_kernel": round(dt_u / dt_m2, 3),
        "mfu": None,
        "note": ("baseline = exact masked attention (materializes "
                 "TxT with -inf bias) — what variable-length batches "
                 "fall back to without kv-mask-aware kernels; "
                 "vs_unmasked_kernel isolates the mask operand's "
                 "overhead (1.0 = free). Throughput counts REAL "
                 "(unpadded) tokens only")}


CKPT_HIDDEN = 1024        # ~4.3M params -> ~17MB of f32 to zip
CKPT_LAYERS = 4
CKPT_SAVES = 6
PS_EPOCH_CAP = 40         # per-variant epoch bound for the PS leg


def _leg_checkpoint_async(peak):
    """Robustness-overhead leg: train-thread BLOCKED ms per
    checkpoint save, sync vs the async background writer — the number
    behind the preemption-tolerance claim that checkpointing is off
    the critical path. Sync saves pay snapshot + npz + DEFLATE + zip
    + rename on the train thread; async saves pay only the
    device→host snapshot and the writer handoff. The async p99 comes
    from the checkpoint_write_seconds{phase="blocked"} histogram
    itself (reset before the async phase so it holds async samples
    only), so the committed number is the same instrument operators
    scrape."""
    import shutil
    import tempfile

    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                   OutputLayer)
    from deeplearning4j_tpu.observability.registry import REGISTRY
    from deeplearning4j_tpu.train.fault_tolerance import ElasticTrainer

    b = (NeuralNetConfiguration.builder().set_seed(0)
         .updater(updaters.adam(1e-3)).list())
    for _ in range(CKPT_LAYERS):
        b = b.layer(DenseLayer(n_out=CKPT_HIDDEN, activation="relu"))
    conf = (b.layer(OutputLayer(n_out=16))
            .set_input_type(InputType.feed_forward(CKPT_HIDDEN))
            .build())
    net = MultiLayerNetwork(conf).init()
    zip_mb = net.num_params() * 4 / 1e6
    root = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        sync = ElasticTrainer(net, os.path.join(root, "sync"), keep=2,
                              handle_sigterm=False)
        sync_s = []
        for _ in range(CKPT_SAVES):
            net.iteration_count += 1
            t0 = time.perf_counter()
            sync.save_checkpoint()
            sync_s.append(time.perf_counter() - t0)
        # fresh histograms: the p99 reported below must be async-only
        for phase in ("blocked", "total"):
            REGISTRY.unregister("checkpoint_write_seconds",
                                {"phase": phase})
        asy = ElasticTrainer(net, os.path.join(root, "async"), keep=2,
                             handle_sigterm=False,
                             async_checkpoint=True)
        blocked, total = [], []
        for _ in range(CKPT_SAVES):
            net.iteration_count += 1
            t0 = time.perf_counter()
            asy.save_checkpoint()
            blocked.append(time.perf_counter() - t0)
            # barrier per save so total measures one clean write (no
            # coalescing in the measured window)
            asy.checkpoint_barrier()
            total.append(time.perf_counter() - t0)
        asy.close()
        hist = REGISTRY.histogram("checkpoint_write_seconds",
                                  labels={"phase": "blocked"})
        blocked_p99_ms = hist.snapshot()["p99"] * 1e3
    finally:
        shutil.rmtree(root, ignore_errors=True)
    sync_ms = sorted(sync_s)[len(sync_s) // 2] * 1e3
    async_total_ms = sorted(total)[len(total) // 2] * 1e3
    ratio = blocked_p99_ms / sync_ms if sync_ms else None
    print(f"checkpoint_async: sync {sync_ms:.1f} ms/save blocked; "
          f"async blocked p99 {blocked_p99_ms:.2f} ms "
          f"(total {async_total_ms:.1f} ms), zip ~{zip_mb:.0f}MB, "
          f"blocked/sync {ratio:.3f}", file=sys.stderr)
    return {
        "metric": (f"checkpoint save train-thread blocked time "
                   f"(async writer, ~{zip_mb:.0f}MB of f32 params, "
                   f"p99 of {CKPT_SAVES} saves)"),
        "value": round(blocked_p99_ms, 3), "unit": "ms/save",
        "baseline": None, "vs_baseline": None,
        "sync_blocked_ms_per_save": round(sync_ms, 2),
        "async_blocked_ms_p99": round(blocked_p99_ms, 3),
        "async_total_ms_per_save": round(async_total_ms, 2),
        "blocked_over_sync": None if ratio is None
        else round(ratio, 4),
        "note": ("sync saves serialize+zip+rename on the train "
                 "thread; async saves pay device->host snapshot + "
                 "writer handoff only (the writer does the rest off "
                 "thread, one in-flight write, newest-supersedes "
                 "coalescing). Acceptance bar: blocked p99 under 10% "
                 "of the sync write time (blocked_over_sync < 0.1). "
                 "p99 read from the "
                 "checkpoint_write_seconds{phase=blocked} histogram "
                 "after an async-only reset — the operators' own "
                 "instrument, not a bench-local stopwatch")}


def _leg_ps_async_training(peak):
    """Async parameter-server leg: time-to-target-loss for 3 async
    PS workers (int8+EF compressed pushes) vs a synchronous
    single-process SGD loop over the SAME batches, model and rate —
    plus the staleness-vs-accuracy frontier (max_staleness 0 / 4 /
    16 / unbounded). The target is self-calibrating: 80% of the loss
    drop the sync loop achieves inside the epoch cap, so the leg
    measures wall-clock to equivalent progress, not steps. Workers
    are threads (the jitted grad step releases the GIL) against an
    in-process server — the same wire protocol and staleness
    machinery as the multi-process ``train-ps`` CLI, minus process
    spawn noise."""
    import threading

    import jax
    import numpy as np

    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                   OutputLayer)
    from deeplearning4j_tpu.parallel.paramserver import (
        ParameterServer, PSClient, PSWorker)

    N_IN, N_OUT, HIDDEN = 8, 3, 16
    N_BATCHES, BATCH = 24, 16
    LR, EPOCH_CAP, WORKERS = 0.2, PS_EPOCH_CAP, 3

    def net(seed=0):
        conf = (NeuralNetConfiguration.builder().set_seed(seed)
                .updater(updaters.sgd(LR)).list()
                .layer(DenseLayer(n_out=HIDDEN, activation="relu"))
                .layer(OutputLayer(n_out=N_OUT))
                .set_input_type(InputType.feed_forward(N_IN))
                .build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    batches = []
    for _ in range(N_BATCHES):
        c = rng.integers(0, N_OUT, BATCH)
        x = (rng.normal(size=(BATCH, N_IN))
             + c[:, None] * 1.5).astype(np.float32)
        batches.append(DataSet(x, np.eye(N_OUT,
                                         dtype=np.float32)[c]))

    ev_model = net(seed=0)
    ev_batches = [ev_model._batch_tuple(ds) for ds in batches]

    @jax.jit
    def _ev_one(params, batch):
        loss, _ = ev_model._loss(params, ev_model.state, batch,
                                 None, training=False)
        return loss

    def eval_loss(params):
        return float(np.mean([_ev_one(params, b)
                              for b in ev_batches]))

    # -- synchronous baseline: plain SGD, exact (uncompressed) grads
    sync = net(seed=0)
    state = sync.state

    def loss_fn(p, batch, r):
        loss, _ = sync._loss(p, state, batch, r, training=True)
        return loss

    vg = jax.jit(jax.value_and_grad(loss_fn))
    params = sync.params
    init_loss = eval_loss(params)
    key = sync._rng_key
    vg(params, ev_batches[0], key)     # compile outside the clock
    t0 = time.perf_counter()
    sync_curve = []
    for epoch in range(EPOCH_CAP):
        for i, b in enumerate(ev_batches):
            _, g = vg(params, b, jax.random.fold_in(
                key, epoch * N_BATCHES + i))
            params = jax.tree_util.tree_map(
                lambda p, gg: p - LR * gg, params, g)
        sync_curve.append((time.perf_counter() - t0,
                           eval_loss(params)))
    sync_total = time.perf_counter() - t0
    sync_final = sync_curve[-1][1]
    target = init_loss - 0.8 * (init_loss - sync_final)

    def first_crossing(curve):
        for t, l in curve:
            if l <= target:
                return t
        return None

    sync_ttl = first_crossing(sync_curve)

    # -- async PS: workers run to the cap; a monitor thread records
    # the first target crossing from the server's own params
    def run_ps(max_staleness):
        m0 = net(seed=0)
        server = ParameterServer(m0.params, lr=LR,
                                 max_staleness=max_staleness).start()
        crossed = [None]
        stop = threading.Event()
        t0 = time.perf_counter()

        def monitor():
            while not stop.wait(0.05):
                if crossed[0] is None \
                        and eval_loss(server.params_tree()) <= target:
                    crossed[0] = time.perf_counter() - t0

        mon = threading.Thread(target=monitor, name="ps-bench-mon",
                               daemon=True)
        stats = [None] * WORKERS

        def work(i):
            model = m0 if i == 0 else net(seed=i)
            client = PSClient(server.address)
            try:
                stats[i] = PSWorker(model, client,
                                    name=f"ps-bench-{i}").run(
                    batches[i::WORKERS], epochs=EPOCH_CAP)
            finally:
                client.close()

        threads = [threading.Thread(target=work, args=(i,),
                                    name=f"ps-bench-{i}",
                                    daemon=True)
                   for i in range(WORKERS)]
        mon.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        total = time.perf_counter() - t0
        stop.set()
        mon.join(10)
        final = eval_loss(server.params_tree())
        if crossed[0] is None and final <= target:
            crossed[0] = total      # crossed between monitor ticks
        st = dict(server.stats)
        server.stop()
        return {"max_staleness": max_staleness,
                "time_to_target_s": None if crossed[0] is None
                else round(crossed[0], 3),
                "total_s": round(total, 3),
                "final_loss": round(final, 4),
                "stale_rejects": st["pushes_stale"],
                "pushes_applied": st["pushes_applied"]}

    frontier = [run_ps(ms) for ms in (0, 4, 16, None)]
    headline = next(f for f in frontier if f["max_staleness"] == 4)
    ttl = headline["time_to_target_s"]
    print("ps_async_training: sync time-to-target "
          f"{sync_ttl and round(sync_ttl, 2)}s "
          f"(final {sync_final:.4f}); async s=4 time-to-target "
          f"{ttl}s; frontier "
          + ", ".join(f"s={f['max_staleness']}: "
                      f"loss {f['final_loss']} in "
                      f"{f['time_to_target_s']}s"
                      for f in frontier), file=sys.stderr)
    return {
        "metric": (f"async PS time-to-target-loss, {WORKERS} "
                   f"int8+EF workers, max_staleness=4 (target = 80% "
                   f"of the sync loss drop, {N_BATCHES}x{BATCH} "
                   "synthetic 3-class batches)"),
        "value": ttl, "unit": "s",
        "baseline": None if sync_ttl is None else round(sync_ttl, 3),
        "vs_baseline": None if not (ttl and sync_ttl)
        else round(sync_ttl / ttl, 3),
        "target_loss": round(target, 4),
        "init_loss": round(init_loss, 4),
        "sync_final_loss": round(sync_final, 4),
        "sync_total_s": round(sync_total, 3),
        "staleness_frontier": frontier,
        "note": ("vs_baseline is sync/async time-to-target "
                 "(>1 = async reaches equivalent progress faster). "
                 "The frontier shows the bounded-staleness "
                 "accuracy/speed trade: s=0 serializes pushes "
                 "(stale_rejects climb), unbounded runs free. "
                 "Same server/worker/wire stack as `train-ps`; "
                 "workers are in-process threads so the number "
                 "isolates protocol + staleness cost from process "
                 "spawn noise")}


def _kstep_lenet(c1=4, c2=8, dense=64, seed=0):
    """Scaled-down LeNet for the k-step leg: same stack, channel
    counts shrunk so the per-step device compute sits well under the
    host's per-dispatch overhead — the dispatch-bound regime the
    full-size LeNet occupies on TPU (where ~1 ms of compute meets a
    ~1 ms host round-trip), reproduced on whatever host runs the
    leg."""
    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (ConvolutionLayer,
                                                   DenseLayer,
                                                   OutputLayer,
                                                   SubsamplingLayer)
    conf = (NeuralNetConfiguration.builder().set_seed(seed)
            .updater(updaters.adam(1e-3)).list()
            .layer(ConvolutionLayer(n_out=c1, kernel=(5, 5),
                                    activation="relu"))
            .layer(SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
            .layer(ConvolutionLayer(n_out=c2, kernel=(5, 5),
                                    activation="relu"))
            .layer(SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
            .layer(DenseLayer(n_out=dense, activation="relu"))
            .layer(OutputLayer(n_out=10, loss="mcxent"))
            .set_input_type(InputType.convolutional_flat(28, 28, 1))
            .build())
    return MultiLayerNetwork(conf).init()


def _kstep_batch(batch=8, seed=0):
    from deeplearning4j_tpu.data.dataset import DataSet
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 1, (batch, 784)).astype("float32")
    y = np.eye(10, dtype="float32")[rng.integers(0, 10, batch)]
    return DataSet(x, y)


KSTEP_TOTAL = 384          # logical steps per measured k (div by 64)


def _leg_lenet_kstep(peak):
    """k-step fused training on the dispatch-bound LeNet config:
    steps/sec and per-step jitter at k ∈ {1, 8, 64}, every program
    AOT-warmed so no measurement pays a compile. Each fit_batches
    call is one device dispatch covering k steps; the per-call wall
    time / k is the per-step cost whose spread is the jitter the
    ISSUE's MFU analysis flagged (±20% on the per-step path)."""
    ds = _kstep_batch()
    res = {}
    for k in (1, 8, 64):
        net = _kstep_lenet()
        net.warmup(ds, steps_per_device_call=k)
        batches = [ds] * k
        for _ in range(max(2, 16 // k)):            # warm the loop
            net.fit_batches(batches, steps_per_device_call=k)
        per_step = []
        t0 = time.perf_counter()
        for _ in range(KSTEP_TOTAL // k):
            t1 = time.perf_counter()
            net.fit_batches(batches, steps_per_device_call=k)
            per_step.append((time.perf_counter() - t1) / k)
        dt = time.perf_counter() - t0
        srt = sorted(per_step)
        p50 = srt[len(srt) // 2]
        p95 = srt[min(len(srt) - 1, int(len(srt) * 0.95))]
        res[k] = {"steps_per_sec": KSTEP_TOTAL / dt,
                  "step_ms_p50": p50 * 1e3,
                  "step_ms_p95": p95 * 1e3,
                  "jitter_pct": (p95 - p50) / p50 * 100.0}
        print(f"lenet_kstep k={k}: {res[k]['steps_per_sec']:.0f} "
              f"steps/s, p50 {res[k]['step_ms_p50']:.2f} ms, "
              f"jitter (p95-p50)/p50 {res[k]['jitter_pct']:.0f}%",
              file=sys.stderr)
    out = {
        "metric": ("LeNet k-step fused training, dispatch-bound "
                   "config (c4/c8/d64, batch 8): k=8 one-program "
                   "steps/sec vs per-step dispatch"),
        "value": round(res[8]["steps_per_sec"], 1),
        "unit": "steps/sec",
        "baseline": round(res[1]["steps_per_sec"], 1),
        "vs_baseline": round(res[8]["steps_per_sec"]
                             / res[1]["steps_per_sec"], 3),
        "mfu": None,
        "note": ("k steps fused into one lax.scan device program "
                 "(donated carry), AOT-warmed: the host round-trip "
                 "+ dispatch overhead is paid once per k steps. "
                 "Jitter = (p95-p50)/p50 of per-step wall time; the "
                 "fused path also smooths it because k steps share "
                 "one dispatch."),
    }
    for k, r in res.items():
        out[f"k{k}_steps_per_sec"] = round(r["steps_per_sec"], 1)
        out[f"k{k}_step_ms_p50"] = round(r["step_ms_p50"], 3)
        out[f"k{k}_jitter_pct"] = round(r["jitter_pct"], 1)
    return out


def _leg_aot_warmup(peak):
    """AOT warmup: programs compiled at warmup vs ZERO in the steady
    state (train fit windows + tail, and a serving predict burst over
    every pow2 bucket), plus first-call latency warm vs cold. The
    zero-compile claims are asserted with
    compile_watch.zero_compile_scope — the leg FAILS if the steady
    state compiles."""
    from deeplearning4j_tpu.observability.compile_watch import (
        install_global_watch)
    stats = install_global_watch()
    ds = _kstep_batch()

    # cold: first call traces + compiles (the persistent bench cache
    # may soften this on repeat runs — reported as-is)
    net_cold = _kstep_lenet(seed=1)
    t0 = time.perf_counter()
    net_cold.fit_batches([ds])
    cold_first_s = time.perf_counter() - t0

    # warm: lower().compile() both programs up front, then the first
    # call dispatches a ready executable
    net_warm = _kstep_lenet(seed=1)
    mark_w = stats.mark()
    rep = net_warm.warmup(ds, steps_per_device_call=8)
    warmup_stats = stats.summary(mark_w)
    warmup_secs = sum(rep.values())
    t0 = time.perf_counter()
    net_warm.fit_batches([ds])
    warm_first_s = time.perf_counter() - t0

    # steady state: fused windows + a 3-batch tail, zero compiles
    with stats.zero_compile_scope("aot_warmup train steady state"):
        for _ in range(5):
            net_warm.fit_batches([ds] * 8, steps_per_device_call=8)
            net_warm.fit_batches([ds] * 3, steps_per_device_call=8)

    # serving: warm every pow2 bucket, then a mixed-size burst
    from deeplearning4j_tpu.serving.http import ModelServer
    from deeplearning4j_tpu.serving.registry import ModelRegistry
    reg = ModelRegistry()
    reg.register("default", _kstep_lenet(seed=2))
    cold_srv = ModelServer(reg, max_batch_size=8)
    sched, _ = cold_srv.scheduler_for("default")
    x1 = np.zeros((1, 784), np.float32)
    t0 = time.perf_counter()
    sched.predict(x1, timeout=120)
    serve_cold_first_s = time.perf_counter() - t0
    cold_srv.stop(drain=False)

    reg2 = ModelRegistry()
    reg2.register("default", _kstep_lenet(seed=2))
    warm_srv = ModelServer(reg2, max_batch_size=8)
    warm_srv.warmup(generate=False)
    sched2, _ = warm_srv.scheduler_for("default")
    t0 = time.perf_counter()
    sched2.predict(x1, timeout=120)
    serve_warm_first_s = time.perf_counter() - t0
    with stats.zero_compile_scope("aot_warmup serve burst"):
        for n in (1, 2, 3, 5, 8, 7, 4, 1):
            sched2.predict(np.zeros((n, 784), np.float32),
                           timeout=120)
    warm_srv.stop(drain=False)

    print(f"aot_warmup: train first call cold {cold_first_s*1e3:.0f} "
          f"ms vs warm {warm_first_s*1e3:.1f} ms; serve first "
          f"request cold {serve_cold_first_s*1e3:.0f} ms vs warm "
          f"{serve_warm_first_s*1e3:.1f} ms; steady-state compiles "
          "0+0 (asserted)", file=sys.stderr)
    return {
        "metric": ("AOT warmup: first train-step latency, warmed "
                   "(jit().lower(shapes).compile() at startup) vs "
                   "cold first call"),
        "value": round(warm_first_s * 1e3, 2), "unit": "ms",
        "baseline": round(cold_first_s * 1e3, 2),
        "vs_baseline": round(warm_first_s / cold_first_s, 4),
        "mfu": None,
        "programs_compiled_at_warmup": sorted(rep),
        "warmup_compile_secs": round(warmup_secs, 3),
        "warmup_backend_compiles":
            warmup_stats["backend_compiles"],
        "steady_state_backend_compiles": 0,
        "serve_first_request_cold_ms":
            round(serve_cold_first_s * 1e3, 2),
        "serve_first_request_warm_ms":
            round(serve_warm_first_s * 1e3, 2),
        "note": ("steady_state_backend_compiles is ASSERTED zero by "
                 "compile_watch.zero_compile_scope over 5 fused "
                 "windows + k=1 tails AND a mixed-batch-size predict "
                 "burst over pre-warmed pow2 buckets; the leg fails "
                 "if anything compiles. Cold numbers can be softened "
                 "by the persistent XLA cache on repeat bench runs."),
    }


def _leg_multichip_dp_scaling(peak):
    """Mesh-spec sharded training throughput: dp=1 vs dp=2 at k=1 vs
    k=8 on the forced-host-device CPU mesh (the README recipe), every
    program AOT-warmed. Runs in a NESTED subprocess so the forced
    8-device XLA flag applies regardless of how this leg process's
    backend was initialized. On this 2-core host dp=2 shares the same
    two cores, so the leg proves the sharded program path (one SPMD
    program per window, zero steady-state compiles) rather than real
    scaling — the speedup column is the k-fusion win on a mesh."""
    import subprocess
    script = r"""
import json, os, sys, time
import numpy as np
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from deeplearning4j_tpu import (MultiLayerNetwork,
                                NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf import updaters
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.observability.compile_watch import (
    install_global_watch)

def net(seed=0):
    conf = (NeuralNetConfiguration.builder().set_seed(seed)
            .updater(updaters.adam(1e-3)).list()
            .layer(DenseLayer(n_out=64, activation="relu"))
            .layer(DenseLayer(n_out=64, activation="relu"))
            .layer(OutputLayer(n_out=10))
            .set_input_type(InputType.feed_forward(32)).build())
    return MultiLayerNetwork(conf).init()

rng = np.random.default_rng(0)
x = rng.normal(size=(64, 32)).astype(np.float32)
y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 64)]
ds = DataSet(x, y)
TOTAL = 192
stats = install_global_watch()
out = {}
for dp in (1, 2):
    for k in (1, 8):
        m = net(seed=1)
        m.use_mesh(f"dp={dp}")
        m.warmup(ds, steps_per_device_call=k)
        batches = [ds] * k
        for _ in range(max(2, 16 // k)):            # warm the loop
            m.fit_batches(batches, steps_per_device_call=k)
        t0 = time.perf_counter()
        with stats.zero_compile_scope(f"dp={dp} k={k} steady"):
            for _ in range(TOTAL // k):
                m.fit_batches(batches, steps_per_device_call=k)
        dt = time.perf_counter() - t0
        out[f"dp{dp}_k{k}_steps_per_sec"] = round(TOTAL / dt, 1)
print(json.dumps(out))
"""
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          cwd=here, capture_output=True, text=True,
                          timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(
            f"multichip subprocess failed: {proc.stderr[-2000:]}")
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    print("multichip_dp_scaling: "
          + ", ".join(f"{k}={v}" for k, v in res.items()),
          file=sys.stderr)
    return {
        "metric": ("mesh-spec sharded training steps/sec, 3-layer "
                   "MLP (d64/d64/out10, batch 64) on the forced "
                   "8-host-device CPU mesh: dp=2 fused k=8 windows "
                   "vs per-step"),
        "value": res["dp2_k8_steps_per_sec"],
        "unit": "steps/sec",
        "baseline": res["dp2_k1_steps_per_sec"],
        "vs_baseline": round(res["dp2_k8_steps_per_sec"]
                             / res["dp2_k1_steps_per_sec"], 3),
        "mfu": None,
        **res,
        "note": ("fit(mesh_spec='dp=N') + steps_per_device_call=k: "
                 "one SPMD device program per fused window, AOT-"
                 "warmed, zero steady-state compiles ASSERTED per "
                 "config (the leg fails if anything compiles). "
                 "This 2-core host runs every forced 'device' on "
                 "the same two cores, so dp=2 cannot beat dp=1 "
                 "here — the leg pins the sharded-path overhead and "
                 "the k-fusion multiplier on a mesh; real dp "
                 "scaling needs real chips."),
    }


DISAGG_V, DISAGG_D, DISAGG_H = 64, 32, 2
DISAGG_CAP, DISAGG_PS = 96, 16
DISAGG_PROMPT, DISAGG_TOKENS = 32, 8
DISAGG_REQUESTS = 160
DISAGG_CONC = 4


def _leg_disagg_kv_routing(peak):
    """KV-aware (prefix-fingerprint) routing vs the affinity-only
    router over a 4-replica in-process fleet under a
    ``--dup-ratio 0.5`` duplicate-prompt generate mix: the KV-aware
    router sends a repeated prompt to the replica whose prefix cache
    already holds it, so the fleet-wide prefix-hit ratio rises and
    the duplicate population's TTFT collapses to the hit path.
    Everything here shares one process (replicas + router + GIL), so
    the honest read is the RATIO between the two router modes in the
    same harness, plus the hit-vs-cold TTFT split scraped from the
    replicas' own ``serving_ttft_seconds{population=...}``
    histograms."""
    import subprocess
    import urllib.request

    from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (
        EmbeddingSequenceLayer, RnnOutputLayer,
        TransformerEncoderLayer)
    from deeplearning4j_tpu.serving.fleet import ReplicaFleet
    from deeplearning4j_tpu.serving.router import Router
    from tools.loadgen import scrape_ttft_populations

    def lm():
        conf = (NeuralNetConfiguration.builder().set_seed(0)
                .updater(updaters.adam(1e-3)).list()
                .layer(EmbeddingSequenceLayer(n_in=DISAGG_V,
                                              n_out=DISAGG_D))
                .layer(TransformerEncoderLayer(n_heads=DISAGG_H,
                                               causal=True))
                .layer(RnnOutputLayer(n_out=DISAGG_V, loss="mcxent"))
                .set_input_type(InputType.recurrent(DISAGG_V,
                                                    DISAGG_CAP))
                .build())
        return MultiLayerNetwork(conf).init()

    def factory():
        return {"default": lm()}

    def loadgen(port, total):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.loadgen",
             "--url", f"http://127.0.0.1:{port}",
             "--mode", "generate", "--dup-ratio", "0.5",
             "--prompt-len", str(DISAGG_PROMPT),
             "--n-tokens", str(DISAGG_TOKENS),
             "--vocab", str(DISAGG_V),
             "--concurrency", str(DISAGG_CONC),
             "--total", str(total),
             "--timeout", "60", "--retries", "2",
             "--metrics-url", "off"],
            capture_output=True, text=True, timeout=600,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        if not proc.stdout.strip():
            raise RuntimeError(
                f"loadgen exited {proc.returncode} with no report; "
                f"stderr: {proc.stderr[-800:]}")
        return json.loads(proc.stdout)

    def run(kv_routing):
        fleet = ReplicaFleet(
            factory, n=4,
            server_kwargs=dict(slots=4, capacity=DISAGG_CAP,
                               page_size=DISAGG_PS)).start()
        router = Router(fleet, probe_interval_s=0.2,
                        hedge_after_s=None, sample_rate=0.0,
                        request_timeout_s=60.0,
                        kv_routing=kv_routing).start()
        try:
            # warm every replica's compiled decode DIRECTLY (not via
            # the router) with a sub-page prompt: 8 tokens < one
            # 16-token page, so nothing enters any prefix cache and
            # the measured mix starts cold on every replica
            warm = json.dumps({"model": "default",
                               "prompt": list(range(1, 9)),
                               "n_tokens": 2}).encode()
            for r in fleet.snapshot():
                req = urllib.request.Request(
                    r.url + "/v1/generate", data=warm,
                    headers={"Content-Type": "application/json"})
                urllib.request.urlopen(req, timeout=120).read()
            rep = loadgen(router.port, DISAGG_REQUESTS)
            if rep.get("failed"):
                raise RuntimeError(
                    f"disagg_kv_routing dropped requests: "
                    f"{rep['failed']} ({rep.get('errors')})")
            hits = sum(
                s["prefix_cache_hits_total"]
                for s in router.load_signals())
            ttft = scrape_ttft_populations(
                [r.url for r in fleet.snapshot()], timeout_s=10)
            kv_routed = router._kv_routed.value
        finally:
            router.stop()
            fleet.stop(drain=False, timeout=5.0)
        return {"report": rep, "hits": hits, "ttft": ttft,
                "hit_ratio": hits / max(1, rep["ok"]),
                "kv_routed": kv_routed}

    kv = run(True)
    aff = run(False)
    print(f"disagg_kv_routing: KV-aware hit ratio "
          f"{kv['hit_ratio']:.2f} ({int(kv['hits'])}/"
          f"{kv['report']['ok']}, {int(kv['kv_routed'])} "
          f"prefix-routed) vs affinity-only {aff['hit_ratio']:.2f} "
          f"({int(aff['hits'])}/{aff['report']['ok']}); TTFT hit "
          f"p50 {kv['ttft']['prefix_hit']['p50']:.1f} ms vs cold "
          f"p50 {kv['ttft']['cold']['p50']:.1f} ms (baseline cold "
          f"p50 {aff['ttft']['cold']['p50']:.1f} ms)",
          file=sys.stderr)
    return {
        "metric": (f"disagg_kv_routing: fleet-wide prefix-hit "
                   f"ratio under a dup-ratio 0.5 generate mix "
                   f"(4 in-process replicas, prompt "
                   f"{DISAGG_PROMPT}, page {DISAGG_PS}, "
                   f"{DISAGG_REQUESTS} requests) — KV-aware "
                   f"router vs affinity-only"),
        "value": round(kv["hit_ratio"], 3),
        "unit": "prefix-hit ratio",
        "baseline": round(aff["hit_ratio"], 3),
        "vs_baseline": round(
            kv["hit_ratio"] / max(1e-9, aff["hit_ratio"]), 3),
        "kv_routed_requests": int(kv["kv_routed"]),
        "ttft_ms": {
            "kv_hit_p50": kv["ttft"]["prefix_hit"]["p50"],
            "kv_hit_p99": kv["ttft"]["prefix_hit"]["p99"],
            "kv_cold_p50": kv["ttft"]["cold"]["p50"],
            "kv_cold_p99": kv["ttft"]["cold"]["p99"],
            "affinity_hit_p50": aff["ttft"]["prefix_hit"]["p50"],
            "affinity_cold_p50": aff["ttft"]["cold"]["p50"]},
        "hit_counts": {"kv": int(kv["hits"]),
                       "affinity": int(aff["hits"]),
                       "requests": kv["report"]["ok"]},
        "client_latency_ms": {
            "kv_p50": kv["report"]["latency_ms"]["p50"],
            "affinity_p50": aff["report"]["latency_ms"]["p50"]},
        "note": ("replicas, router and their GIL share one "
                 "process on the 2-core host: read the two router "
                 "modes as a controlled A/B, not absolute "
                 "throughput"),
    }


# (name, fn, warm-cache wall estimate sec). Order = priority: the five
# BASELINE.md configs first (VGG before the informational flash leg —
# round-2 lost config 4 to the wall clock with the legs the other way).
RETR_N, RETR_DIM, RETR_CLUSTERS = 8192, 64, 64
RETR_NLIST, RETR_K = 64, 10
RETR_CONC, RETR_QUERIES = 8, 512


def _leg_retrieval_serving(peak):
    """Retrieval serving, two claims. (1) The recall@k-vs-throughput
    FRONTIER: brute-force exact search vs IVF at nprobe 1/4/16
    through the batched search backend, p50/p99 per config, with
    ZERO steady-state compiles asserted after warmup (the pow2
    bucketing + snapshot-constant gather width make the shapes
    static). (2) The SOAK: a 4-replica subprocess fleet serving
    mixed predict + search traffic through the router, one replica
    SIGKILLed mid-run by a seeded chaos fault — zero dropped search
    requests and recall@10 >= 0.9 on the IVF path, measured by
    loadgen's client-side oracle."""
    import subprocess
    import tempfile
    import urllib.request

    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration, chaos)
    from deeplearning4j_tpu.nn.conf import updaters
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                   OutputLayer)
    from deeplearning4j_tpu.observability.compile_watch import (
        install_global_watch)
    from deeplearning4j_tpu.retrieval import (BruteForceIndex,
                                              IVFIndex)
    from deeplearning4j_tpu.serving.fleet import ReplicaFleet
    from deeplearning4j_tpu.serving.metrics import ServingMetrics
    from deeplearning4j_tpu.serving.retrieval_backend import (
        RetrievalService)
    from deeplearning4j_tpu.serving.router import Router
    from deeplearning4j_tpu.util.model_serializer import write_model
    from tools.loadgen import SearchWorkload

    stats = install_global_watch()
    rng = np.random.default_rng(0)
    centers = rng.normal(size=(RETR_CLUSTERS, RETR_DIM))
    assign = rng.integers(0, RETR_CLUSTERS, size=RETR_N)
    vectors = (centers[assign]
               + 0.15 * rng.standard_normal((RETR_N, RETR_DIM))
               ).astype(np.float32)
    ids = np.arange(RETR_N)
    wl = SearchWorkload(vectors, ids=ids, k=RETR_K,
                        metric="cosine", pool=256, seed=1)

    def run_config(label, index, nprobe):
        svc = RetrievalService(index, metrics=ServingMetrics(),
                               max_batch_size=32, wait_ms=1.0)
        try:
            # warm every pow2 batch bucket the closed loop can form
            svc.warmup(ks=(RETR_K,), nprobes=(nprobe,),
                       batch_sizes=(1, 2, 4, 8))
            lock = threading.Lock()
            lat, hits = [], [0, 0]
            per = RETR_QUERIES // RETR_CONC

            def worker(wid):
                for j in range(per):
                    i = wid * per + j
                    r = min(wl.rank_of(i), len(wl.queries) - 1)
                    t0 = time.perf_counter()
                    rids, _ = svc.search(wl.queries[r], k=RETR_K,
                                         nprobe=nprobe, timeout=60.0)
                    dt = time.perf_counter() - t0
                    got = {int(x) for x in rids[0] if x >= 0}
                    h = len(got & wl._oracle[r])
                    with lock:
                        lat.append(dt)
                        hits[0] += h
                        hits[1] += RETR_K

            t0 = time.perf_counter()
            with stats.zero_compile_scope(
                    f"retrieval {label} steady state"):
                threads = [threading.Thread(target=worker, args=(w,),
                                            daemon=True)
                           for w in range(RETR_CONC)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
            wall = time.perf_counter() - t0
            lat.sort()
            return {"config": label, "nprobe": nprobe,
                    "qps": round(len(lat) / wall, 1),
                    "p50_ms": round(lat[len(lat) // 2] * 1e3, 3),
                    "p99_ms": round(
                        lat[min(len(lat) - 1,
                                int(len(lat) * 0.99))] * 1e3, 3),
                    "recall_at_10": round(hits[0] / hits[1], 4),
                    "steady_state_backend_compiles": 0}
        finally:
            svc.close(drain=False)

    brute = BruteForceIndex(RETR_DIM, metric="cosine")
    brute.add(ids, vectors)
    ivf = IVFIndex(RETR_DIM, nlist=RETR_NLIST, metric="cosine")
    ivf.build(ids, vectors)
    frontier = [run_config("brute_force", brute, None)]
    for nprobe in (1, 4, 16):
        frontier.append(run_config(f"ivf_nprobe{nprobe}", ivf,
                                   nprobe))
    for row in frontier:
        print(f"retrieval frontier: {row['config']} "
              f"{row['qps']:.0f} q/s p50 {row['p50_ms']:.1f} ms "
              f"p99 {row['p99_ms']:.1f} ms recall@10 "
              f"{row['recall_at_10']:.3f}", file=sys.stderr)

    # ---- soak: 4 subprocess replicas, mixed traffic, SIGKILL ----
    feat, hidden, classes = 16, 32, 4
    conf = (NeuralNetConfiguration.builder().set_seed(0)
            .updater(updaters.adam(1e-3)).list()
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_out=classes, loss="mcxent"))
            .set_input_type(InputType.feed_forward(feat)).build())
    tmp = tempfile.mkdtemp(prefix="bench_retr_")
    model_zip = os.path.join(tmp, "mlp.zip")
    write_model(MultiLayerNetwork(conf).init(), model_zip)
    corpus = (f"random:n=4096,dim=32,seed=11,clusters="
              f"{RETR_CLUSTERS // 2}")

    def loadgen(router_port, mode, total, out):
        cmd = [sys.executable, "-m", "tools.loadgen",
               "--url", f"http://127.0.0.1:{router_port}",
               "--concurrency", "8", "--total", str(total),
               "--timeout", "30", "--retries", "3"]
        if mode == "search":
            cmd += ["--mode", "search", "--corpus", corpus,
                    "--k", str(RETR_K), "--metric", "cosine"]
        else:
            cmd += ["--features", str(feat)]
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=300,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        if not proc.stdout.strip():
            raise RuntimeError(
                f"loadgen {mode} exited {proc.returncode} with no "
                f"report; stderr: {proc.stderr[-800:]}")
        out[mode] = json.loads(proc.stdout)

    fleet = ReplicaFleet(
        model_specs=[f"default={model_zip}"], n=4, base_port=18350,
        extra_args=["--index", corpus, "--index-kind", "ivf",
                    "--nlist", str(RETR_NLIST // 2),
                    "--nprobe", "8"]).start()
    router = Router(fleet, probe_interval_s=0.25, hedge_after_s=None,
                    sample_rate=0.0).start()
    try:
        deadline = time.monotonic() + 180.0
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{router.port}/healthz",
                        timeout=5.0) as r:
                    if json.load(r).get("eligible") == 4:
                        break
            except OSError:
                pass
            time.sleep(0.25)
        else:
            raise RuntimeError("retrieval fleet never became ready")
        # warmup both routes outside the measured window
        warm: dict = {}
        loadgen(router.port, "predict", 128, warm)
        loadgen(router.port, "search", 128, warm)
        chaos.install({"faults": [
            {"site": "serving.replica", "kind": "kill",
             "at": [200], "args": {"replica": 0}}]}, seed=1234)
        reports: dict = {}
        threads = [threading.Thread(
            target=loadgen,
            args=(router.port, mode, 400, reports), daemon=True)
            for mode in ("predict", "search")]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300.0)
    finally:
        chaos.uninstall()
        router.stop()
        fleet.stop(drain=False, timeout=5.0)
    sr, pr = reports["search"], reports["predict"]
    soak_recall = sr["search"]["recall_at_k"]
    if sr["failed"] or pr["failed"]:
        raise RuntimeError(
            f"retrieval soak dropped requests: search="
            f"{sr['failed']} ({sr['errors']}) predict="
            f"{pr['failed']} ({pr['errors']})")
    if soak_recall is None or soak_recall < 0.9:
        raise RuntimeError(
            f"retrieval soak recall@10 {soak_recall} < 0.9")
    print(f"retrieval soak: search {sr['achieved_qps']:.0f} q/s "
          f"p99 {sr['latency_ms']['p99']:.1f} ms recall@10 "
          f"{soak_recall:.3f}, predict {pr['achieved_qps']:.0f} "
          f"q/s — 0 dropped through SIGKILL", file=sys.stderr)
    ivf16 = next(r for r in frontier
                 if r["config"] == "ivf_nprobe16")
    return {
        "metric": (f"retrieval serving: IVF nprobe=16 search QPS "
                   f"through the batched backend ({RETR_CONC} "
                   f"closed-loop clients, {RETR_N} vectors, dim "
                   f"{RETR_DIM}, k={RETR_K})"),
        "value": ivf16["qps"], "unit": "queries/sec",
        "baseline": frontier[0]["qps"],
        "vs_baseline": round(ivf16["qps"]
                             / max(frontier[0]["qps"], 1e-9), 3),
        "recall_qps_frontier": frontier,
        "soak": {
            "replicas": 4, "sigkill_at_ordinal": 200,
            "search_qps": sr["achieved_qps"],
            "search_p99_ms": sr["latency_ms"]["p99"],
            "search_dropped": sr["failed"],
            "search_retries": sr["retries"],
            "predict_qps": pr["achieved_qps"],
            "predict_dropped": pr["failed"],
            "recall_at_10": soak_recall},
        "host_cpus": os.cpu_count(),
        "mfu": None,
        "note": ("frontier: recall@10 vs QPS for brute-force exact "
                 "search (the baseline) vs IVF at nprobe 1/4/16, "
                 "one in-process RetrievalService per config, "
                 "steady-state compiles ASSERTED zero after warmup "
                 "(zero_compile_scope fails the leg otherwise); "
                 "clustered gaussian corpus. soak: 4 subprocess "
                 "replicas each hosting the same IVF index behind "
                 "the router, concurrent predict + Zipf search "
                 "loadgens, replica 0 SIGKILLed by a seeded "
                 "serving.replica chaos fault mid-run — zero "
                 "dropped requests on either route and recall@10 "
                 ">= 0.9 are asserted, recall measured client-side "
                 "against the exact oracle. Loopback HTTP, one "
                 "host: QPS measures the stack, not scale-out")}


_LEGS = [
    ("resnet_f32", _leg_resnet_f32, 420),
    ("resnet_bf16", _leg_resnet_bf16, 420),
    ("vgg16_import", _leg_vgg16_import, 600),
    ("lenet", _leg_lenet, 180),
    ("char_rnn", _leg_char_rnn, 240),
    ("transformer_lm", _leg_transformer_lm, 300),
    ("flash_attention", _leg_flash_attention, 300),
    ("flash_attention_masked", _leg_flash_attention_masked, 300),
    ("transformer_decode", _leg_transformer_decode, 300),
    # small config (CPU-feasible): paged vs dense decode, prefix-hit
    # TTFT, speculative vs vanilla, fixed-memory slot count
    ("transformer_decode_paged", _leg_transformer_decode_paged, 300),
    ("serving_throughput", _leg_serving_throughput, 180),
    # 480s: its ResNet executable (n_classes=10) is NOT covered by
    # the other ResNet legs' compile cache
    ("resnet_native_etl", _leg_resnet_native_etl, 480),
    # host-side (no device step in the loop): cheap, runs last
    ("checkpoint_async", _leg_checkpoint_async, 120),
    # CPU-dominated (tiny MLP, loopback TCP PS + worker threads):
    # time-to-target-loss vs sync + the staleness frontier
    ("ps_async_training", _leg_ps_async_training, 240),
    # CPU-dominated (tiny models, dispatch path): cheap, runs last
    ("lenet_kstep", _leg_lenet_kstep, 240),
    # nested subprocess with the forced 8-host-device mesh: cheap,
    # CPU-only by construction
    ("multichip_dp_scaling", _leg_multichip_dp_scaling, 240),
    ("aot_warmup", _leg_aot_warmup, 180),
    # CPU-dominated (tiny MLP, scheduler hot path): cheap, runs last
    ("tracing_overhead", _leg_tracing_overhead, 180),
    # CPU-dominated (loopback HTTP, tiny MLP replicas): cheap
    ("router_fleet", _leg_router_fleet, 240),
    # CPU-dominated (loopback HTTP, tiny transformer replicas):
    # the KV-aware vs affinity-only router A/B
    ("disagg_kv_routing", _leg_disagg_kv_routing, 300),
    # CPU-dominated (loopback HTTP, subprocess replicas): collector
    # scrape on/off A/B over the router_fleet harness
    ("observability_overhead", _leg_observability_overhead, 240),
    # CPU-dominated (sleep-based replicas, control-loop timing):
    # cheap, runs last
    ("autoscaler_soak", _leg_autoscaler_soak, 240),
    # CPU-dominated (in-process replicas, control-loop timing):
    # good-canary promotion + bad-canary detect->rollback
    ("rollout_soak", _leg_rollout_soak, 240),
    # CPU-dominated (matmul top-k on tiny corpora, loopback HTTP):
    # the recall-vs-QPS frontier + SIGKILL search soak
    ("retrieval_serving", _leg_retrieval_serving, 300),
]

_LEG_FNS = {n: f for n, f, _ in _LEGS}


def _run_leg(name):
    """One leg on this process's devices: its result dict, labelled
    with the platform, device kind and device count it ran on."""
    import jax
    fn = _LEG_FNS[name]
    platform, kind, peak = _device()
    cfg = fn(peak)
    cfg.update(platform=platform, device_kind=kind,
               device_count=len(jax.devices()))
    return cfg


def _leg_main(name):
    """The ``--leg NAME`` child: compile cache and compile accounting
    set up BEFORE first backend use (config is per-process, and every
    compile in the leg must be counted), then the leg, then its one
    JSON line on stdout."""
    if name not in _LEG_FNS:
        raise SystemExit(f"unknown leg {name!r}; legs: "
                         + ", ".join(_LEG_FNS))
    from deeplearning4j_tpu.observability.compile_watch import (
        install_global_watch)
    from deeplearning4j_tpu.util.platform import setup_compile_cache
    setup_compile_cache()
    compile_stats = install_global_watch()
    try:
        cfg = _run_leg(name)
    except ImportError as e:
        # missing optional dependency (keras/h5py): a clean SKIP —
        # rc 3 tells the runner it is not a failed measurement
        print(f"{name}: dependency unavailable: {e}", file=sys.stderr)
        raise SystemExit(3)
    s = compile_stats.summary()
    cfg["compile_cache_hit"] = s["cache_hit"]
    cfg["compile_stats"] = {
        k: s[k] for k in ("backend_compiles", "compile_secs",
                          "cache_requests", "persistent_cache_hits")}
    print(json.dumps(cfg), flush=True)


_PROBE = ("import json, jax\n"
          "d = jax.devices()\n"
          "print(json.dumps({'platform': d[0].platform, "
          "'kind': d[0].device_kind, 'count': len(d)}))\n")


def _probe_device():
    """The devices a leg child will see, asked of a CHILD: the runner
    must not hold the chip itself, or every leg is locked out."""
    out = subprocess.run([sys.executable, "-c", _PROBE], check=True,
                         stdout=subprocess.PIPE, timeout=300).stdout
    return json.loads(out.decode().strip().splitlines()[-1])


def _run_leg_child(name, timeout):
    """``--leg name`` in a child of its own (stdout and stderr are
    this process's). Returns 'ok', 'skip' (optional dependency
    missing) or 'fail'."""
    try:
        rc = subprocess.run([sys.executable, os.path.abspath(__file__),
                             "--leg", name], timeout=timeout).returncode
    except subprocess.TimeoutExpired:
        print(f"{name} leg timed out ({timeout:.0f}s)", file=sys.stderr)
        return "fail"
    if rc == 3:
        return "skip"
    if rc != 0:
        print(f"{name} leg failed rc={rc}", file=sys.stderr)
        return "fail"
    return "ok"


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if "--leg" in argv:
        _leg_main(argv[argv.index("--leg") + 1])
        return 0
    dev = _probe_device()
    if dev["platform"] != "tpu":
        print(f"bench.py measures on a TPU; jax found {dev} — "
              "nothing was measured", file=sys.stderr)
        return 1
    headline_only = ("--headline-only" in argv
                     or os.environ.get("BENCH_HEADLINE_ONLY") == "1")
    failed = []
    for name, _fn, estimate in (_LEGS[:1] if headline_only else _LEGS):
        # one child at a time: the chip belongs to one process
        if _run_leg_child(name, timeout=2 * estimate) == "fail":
            failed.append(name)
    if failed:
        print("failed legs: " + ", ".join(failed), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
