"""Generate committed DL4J ModelSerializer-format fixtures.

There is no JVM/nd4j in this environment, so authentic reference zips
cannot be produced; these are hand-encoded to the container layout of
util/ModelSerializer.java:79-127 (configuration.json + coefficients.bin
with Nd4j.write framing) and the flat param layouts of nn/params/*.java
— the same pinning approach the reference's own regression tests use
against committed zips (RegressionTest080.java), with the MLP fixture
mirroring 080_ModelSerializer_Regression_MLP_1 (Dense relu 3->4 +
Output softmax/mcxent 4->5, Nesterovs lr=0.15 momentum=0.9, params =
linspace(1..numParams)) so the layout assertions are analytic, not
self-referential.

Run from the repo root:
    JAX_PLATFORMS=cpu python tests/make_dl4j_fixtures.py
"""
import io
import json
import os
import sys
import zipfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeplearning4j_tpu.modelimport.dl4j import write_nd4j_array  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "fixtures", "dl4j")


def _conf(layer_confs, **net_fields):
    d = {
        "backprop": True,
        "pretrain": False,
        "backpropType": "Standard",
        "confs": [
            {
                "iterationCount": 0,
                "minimize": True,
                "miniBatch": True,
                "maxNumLineSearchIterations": 5,
                "numIterations": 1,
                "optimizationAlgo": "STOCHASTIC_GRADIENT_DESCENT",
                "seed": 12345,
                "variables": [],
                "layer": lc,
            }
            for lc in layer_confs
        ],
    }
    d.update(net_fields)
    return d


def _zip(path, conf_dict, flat_params, updater_state=None):
    buf = io.BytesIO()
    # the reference writes the flat vector as a [1, n] row (MLN params())
    write_nd4j_array(buf, np.asarray(flat_params, np.float32)[None, :],
                     order="f")

    def entry(name):
        # fixed timestamp: regeneration must be byte-reproducible so
        # fixture diffs are content-only, never zip-metadata churn
        return zipfile.ZipInfo(name, date_time=(2017, 1, 1, 0, 0, 0))

    with zipfile.ZipFile(path, "w") as zf:
        zf.writestr(entry("configuration.json"),
                    json.dumps(conf_dict, indent=2))
        zf.writestr(entry("coefficients.bin"), buf.getvalue())
        if updater_state is not None:
            ubuf = io.BytesIO()
            write_nd4j_array(
                ubuf, np.asarray(updater_state, np.float32)[None, :],
                order="f")
            zf.writestr(entry("updaterState.bin"), ubuf.getvalue())
    print(f"wrote {path} ({len(flat_params)} params)")


def mlp_fixture():
    """Mirror of 080_ModelSerializer_Regression_MLP_1 (RegressionTest080
    .java:41-83): legacy updater fields + legacy activationFunction
    strings; params = linspace(1..numParams)."""
    conf = _conf([
        {"dense": {
            "activationFunction": "relu",
            "nin": 3, "nout": 4,
            "weightInit": "XAVIER",
            "biasInit": 0.0,
            "updater": "NESTEROVS",
            "learningRate": 0.15,
            "momentum": 0.9,
            "rho": 0.0,
            "l1": 0.0, "l2": 0.0,
        }},
        {"output": {
            "activationFunction": "softmax",
            "lossFunction": "MCXENT",
            "nin": 4, "nout": 5,
            "weightInit": "XAVIER",
            "updater": "NESTEROVS",
            "learningRate": 0.15,
            "momentum": 0.9,
            "rho": 0.0,
        }},
    ])
    n = 3 * 4 + 4 + 4 * 5 + 5
    # updater state = Nesterovs momentum, linspace(1..stateSize) — the
    # reference's own regression test asserts exactly this
    # (RegressionTest080.java:80-83: Nd4j.linspace(1, updaterSize, ...))
    _zip(os.path.join(OUT, "mlp_nesterovs.zip"), conf,
         np.linspace(1, n, n), updater_state=np.linspace(1, n, n))


def conv_fixture():
    """conv (bias-first, 'c'-order W) -> max pool -> batchnorm-free dense
    path with a cnnToFeedForward preprocessor; modern wrapper-object
    activationFn spelling + iUpdater object (post-legacy serde)."""
    rng = np.random.default_rng(7)
    conf = _conf([
        {"convolution": {
            "activationFn": {"ReLU": {}},
            "nin": 2, "nout": 3,
            "kernelSize": [2, 2], "stride": [1, 1], "padding": [0, 0],
            "dilation": [1, 1],
            "convolutionMode": "Truncate",
            "hasBias": True,
            "weightInit": "XAVIER",
            "iUpdater": {"@class": "org.nd4j.linalg.learning.config.Adam",
                         "learningRate": 0.01, "beta1": 0.9,
                         "beta2": 0.999, "epsilon": 1e-8},
        }},
        {"subsampling": {
            "poolingType": "MAX",
            "kernelSize": [2, 2], "stride": [2, 2], "padding": [0, 0],
            "convolutionMode": "Truncate",
        }},
        {"batchNormalization": {
            "activationFn": {"Identity": {}},
            "nin": 3, "nout": 3,
            "decay": 0.9, "eps": 1e-5,
            "lockGammaBeta": False,
        }},
        {"output": {
            "activationFn": {"Softmax": {}},
            "lossFn": {"@class":
                       "org.nd4j.linalg.lossfunctions.impl.LossMCXENT"},
            "nin": 12, "nout": 4,
            "weightInit": "XAVIER",
            "iUpdater": {"@class": "org.nd4j.linalg.learning.config.Adam",
                         "learningRate": 0.01},
        }},
    ], inputPreProcessors={
        "3": {"cnnToFeedForward": {"inputHeight": 2, "inputWidth": 2,
                                   "numChannels": 3}},
    })
    # flat layout: conv b(3) + W(3*2*2*2 'c') ; bn gamma(3) beta(3)
    # mean(3) var(3) ; output W(12*4 'f') + b(4)
    parts = [
        rng.normal(0, 0.5, 3),                     # conv bias
        rng.normal(0, 0.5, 3 * 2 * 2 * 2),         # conv W 'c'
        rng.normal(1.0, 0.1, 3),                   # gamma
        rng.normal(0, 0.1, 3),                     # beta
        rng.normal(0, 0.2, 3),                     # running mean
        np.abs(rng.normal(1.0, 0.1, 3)),           # running var
        rng.normal(0, 0.5, 12 * 4),                # out W 'f'
        rng.normal(0, 0.5, 4),                     # out b
    ]
    _zip(os.path.join(OUT, "conv_pool_bn.zip"), conf,
         np.concatenate(parts))


def lstm_fixture():
    """gravesLSTM (iW/rW 'f' order, (g,f,o,i) gate blocks, 3 peephole
    cols) + rnnoutput."""
    rng = np.random.default_rng(11)
    conf = _conf([
        {"gravesLSTM": {
            "activationFn": {"TanH": {}},
            "gateActivationFn": {"Sigmoid": {}},
            "nin": 3, "nout": 4,
            "forgetGateBiasInit": 1.0,
            "weightInit": "XAVIER",
            "updater": "SGD", "learningRate": 0.1, "rho": 0.0,
        }},
        {"rnnoutput": {
            "activationFn": {"Softmax": {}},
            "lossFunction": "MCXENT",
            "nin": 4, "nout": 3,
            "weightInit": "XAVIER",
            "updater": "SGD", "learningRate": 0.1, "rho": 0.0,
        }},
    ])
    n = 4
    parts = [
        rng.normal(0, 0.4, 3 * 4 * n),        # iW 'f' [3, 4n]
        rng.normal(0, 0.4, n * (4 * n + 3)),  # rW 'f' [n, 4n+3]
        rng.normal(0, 0.4, 4 * n),            # bias
        rng.normal(0, 0.4, n * 3 + 3),        # rnnoutput W 'f' + b
    ]
    _zip(os.path.join(OUT, "graves_lstm.zip"), conf,
         np.concatenate(parts))


def graph_fixture():
    """ComputationGraph zip: diamond DAG (in -> dense a / dense b ->
    merge -> output). Flat params follow the REFERENCE topological order
    (Kahn FIFO seeded by networkInputs, children in vertexInputs
    insertion order — ComputationGraphConfiguration.topologicalOrdering
    :410, param slicing ComputationGraph.init():455): a, b, out."""
    rng = np.random.default_rng(19)

    def layer_vertex(ltype, node):
        return {"LayerVertex": {
            "layerConf": {"layer": {ltype: node}},
            "preProcessor": None, "outputVertex": ltype == "output"}}

    conf = {
        "backprop": True, "pretrain": False, "backpropType": "Standard",
        "networkInputs": ["in"],
        "networkOutputs": ["out"],
        "vertices": {
            "a": layer_vertex("dense", {
                "activationFunction": "relu", "nin": 4, "nout": 5,
                "weightInit": "XAVIER", "updater": "SGD",
                "learningRate": 0.1, "rho": 0.0}),
            "b": layer_vertex("dense", {
                "activationFunction": "tanh", "nin": 4, "nout": 5,
                "weightInit": "XAVIER", "updater": "SGD",
                "learningRate": 0.1, "rho": 0.0}),
            "m": {"MergeVertex": {}},
            "out": layer_vertex("output", {
                "activationFunction": "softmax", "lossFunction": "MCXENT",
                "nin": 10, "nout": 3, "weightInit": "XAVIER",
                "updater": "SGD", "learningRate": 0.1, "rho": 0.0}),
        },
        "vertexInputs": {"a": ["in"], "b": ["in"], "m": ["a", "b"],
                         "out": ["m"]},
        "defaultConfiguration": {"seed": 12345},
    }
    parts = [
        rng.normal(0, 0.5, 4 * 5), rng.normal(0, 0.5, 5),   # a: W 'f', b
        rng.normal(0, 0.5, 4 * 5), rng.normal(0, 0.5, 5),   # b
        rng.normal(0, 0.5, 10 * 3), rng.normal(0, 0.5, 3),  # out
    ]
    _zip(os.path.join(OUT, "graph_diamond.zip"), conf,
         np.concatenate(parts))


def expected_outputs():
    """Forward each fixture on a fixed input and commit the outputs —
    the regression pin (SURVEY.md §4 serialization regression pattern)."""
    from deeplearning4j_tpu.modelimport.dl4j import (
        restore_multi_layer_network,
    )
    from deeplearning4j_tpu.nn import inputs as it

    rng = np.random.default_rng(3)
    out = {}

    net = restore_multi_layer_network(os.path.join(OUT, "mlp_nesterovs.zip"))
    x = rng.normal(0, 1, (4, 3)).astype(np.float32)
    out["mlp_x"], out["mlp_y"] = x, net.output(x)

    net = restore_multi_layer_network(
        os.path.join(OUT, "conv_pool_bn.zip"),
        input_type=it.convolutional(5, 5, 2))
    xc = rng.normal(0, 1, (2, 5, 5, 2)).astype(np.float32)
    out["conv_x"], out["conv_y"] = xc, net.output(xc)

    net = restore_multi_layer_network(os.path.join(OUT, "graves_lstm.zip"))
    xl = rng.normal(0, 1, (2, 6, 3)).astype(np.float32)
    out["lstm_x"], out["lstm_y"] = xl, net.output(xl)

    from deeplearning4j_tpu.modelimport.dl4j import restore_computation_graph

    cg = restore_computation_graph(os.path.join(OUT, "graph_diamond.zip"))
    xg = rng.normal(0, 1, (3, 4)).astype(np.float32)
    out["graph_x"], out["graph_y"] = xg, cg.output(xg)

    np.savez(os.path.join(OUT, "expected_outputs.npz"), **out)
    print("wrote expected_outputs.npz:",
          {k: np.asarray(v).shape for k, v in out.items()})


def normalizer_fixture():
    """The MLP fixture zip + a `normalizer.bin` entry, layout per
    ModelSerializer.addNormalizerToModel (:585) and the nd4j
    NormalizerSerializer STANDARDIZE strategy: the restore test asserts
    these analytic mean/std values come back and flow through
    transform() before output(). A second zip re-encodes the MLP
    coefficients as HALF elements (nd4j DataBuffer.Type.HALF — fp16
    checkpoints), expected to import with fp16-rounded weights."""
    import zipfile as zf_mod

    from deeplearning4j_tpu.datasets.normalizers import (
        NormalizerStandardize,
    )
    from deeplearning4j_tpu.modelimport.dl4j import write_normalizer

    src = os.path.join(OUT, "mlp_nesterovs.zip")

    def entry(name):
        return zf_mod.ZipInfo(name, date_time=(2017, 1, 1, 0, 0, 0))

    norm = NormalizerStandardize()
    norm.mean = np.asarray([0.5, -1.0, 2.0], np.float32)
    norm.std = np.asarray([2.0, 0.5, 1.0], np.float32)
    nbuf = io.BytesIO()
    write_normalizer(nbuf, norm)
    with zf_mod.ZipFile(src) as zin, \
            zf_mod.ZipFile(os.path.join(OUT, "mlp_with_normalizer.zip"),
                           "w") as zout:
        for name in zin.namelist():
            zout.writestr(entry(name), zin.read(name))
        zout.writestr(entry("normalizer.bin"), nbuf.getvalue())
    print("wrote mlp_with_normalizer.zip")

    with zf_mod.ZipFile(src) as zin, \
            zf_mod.ZipFile(os.path.join(OUT, "mlp_half.zip"), "w") as zout:
        for name in zin.namelist():
            if name == "coefficients.bin":
                flat = __import__(
                    "deeplearning4j_tpu.modelimport.dl4j",
                    fromlist=["x"]).read_nd4j_array(
                        io.BytesIO(zin.read(name)))
                hbuf = io.BytesIO()
                write_nd4j_array(hbuf, flat, order="f", dtype="HALF")
                zout.writestr(entry(name), hbuf.getvalue())
            else:
                zout.writestr(entry(name), zin.read(name))
    print("wrote mlp_half.zip")


if __name__ == "__main__":
    os.makedirs(OUT, exist_ok=True)
    mlp_fixture()
    conv_fixture()
    lstm_fixture()
    graph_fixture()
    normalizer_fixture()
    expected_outputs()
