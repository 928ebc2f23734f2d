"""Op correctness via the OpTest harness — numpy references + numeric
gradient checks (reference: ~250 test_*_op.py files; representative set)."""

import numpy as np
import pytest

from op_test import OpTest


class TestMulOp(OpTest):
    op_type = "mul"

    def setup(self):
        rng = np.random.RandomState(0)
        x = rng.uniform(-1, 1, (4, 5)).astype("float32")
        y = rng.uniform(-1, 1, (5, 3)).astype("float32")
        self.inputs = {"X": x, "Y": y}
        self.attrs = {}
        self.outputs = {"Out": x @ y}

    def test_output(self):
        self.setup()
        self.check_output()

    def test_grad(self):
        self.setup()
        self.check_grad(["X", "Y"], "Out")


class TestSoftmaxOp(OpTest):
    op_type = "softmax"

    def setup(self):
        rng = np.random.RandomState(1)
        x = rng.uniform(-1, 1, (3, 7)).astype("float32")
        e = np.exp(x - x.max(axis=-1, keepdims=True))
        self.inputs = {"X": x}
        self.outputs = {"Out": e / e.sum(axis=-1, keepdims=True)}

    def test_output(self):
        self.setup()
        self.check_output()

    def test_grad(self):
        self.setup()
        self.check_grad(["X"], "Out", max_relative_error=0.01)


class TestElementwiseAddBroadcast(OpTest):
    op_type = "elementwise_add"

    def setup(self):
        rng = np.random.RandomState(2)
        x = rng.rand(2, 3, 4).astype("float32")
        y = rng.rand(3, 4).astype("float32")
        self.inputs = {"X": x, "Y": y}
        self.outputs = {"Out": x + y}

    def test_output(self):
        self.setup()
        self.check_output()

    def test_grad(self):
        self.setup()
        self.check_grad(["X", "Y"], "Out")


class TestLayerNormOp(OpTest):
    op_type = "layer_norm"

    def setup(self):
        rng = np.random.RandomState(3)
        N, D = 3, 8
        x = rng.rand(N, D).astype("float32")
        scale = rng.rand(D).astype("float32")
        bias = rng.rand(D).astype("float32")
        mu = x.mean(axis=1, keepdims=True)
        var = x.var(axis=1, keepdims=True)
        y = (x - mu) / np.sqrt(var + 1e-5) * scale + bias
        self.inputs = {"X": x, "Scale": scale, "Bias": bias}
        self.attrs = {"epsilon": 1e-5, "begin_norm_axis": 1}
        self.outputs = {
            "Y": y,
            "Mean": mu.reshape(N),
            "Variance": var.reshape(N),
        }

    def test_output(self):
        self.setup()
        self.check_output(atol=1e-4)


class TestTransposeOp(OpTest):
    op_type = "transpose"

    def setup(self):
        rng = np.random.RandomState(4)
        x = rng.rand(2, 3, 4).astype("float32")
        self.inputs = {"X": x}
        self.attrs = {"axis": [1, 0, 2]}
        self.outputs = {"Out": x.transpose(1, 0, 2)}

    def test_output(self):
        self.setup()
        self.check_output()

    def test_grad(self):
        self.setup()
        self.check_grad(["X"], "Out")


class TestConv2dOp(OpTest):
    op_type = "conv2d"

    def setup(self):
        rng = np.random.RandomState(5)
        x = rng.rand(1, 2, 5, 5).astype("float32")
        w = rng.rand(3, 2, 3, 3).astype("float32")
        out = np.zeros((1, 3, 3, 3), dtype="float32")
        for o in range(3):
            for i in range(3):
                for j in range(3):
                    out[0, o, i, j] = np.sum(
                        x[0, :, i : i + 3, j : j + 3] * w[o]
                    )
        self.inputs = {"Input": x, "Filter": w}
        self.attrs = {
            "strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1],
            "groups": 1,
        }
        self.outputs = {"Output": out}

    def test_output(self):
        self.setup()
        self.check_output(atol=1e-4)

    def test_grad(self):
        self.setup()
        self.check_grad(
            ["Input", "Filter"], "Output", max_relative_error=0.02,
            numeric_grad_delta=5e-3,
        )


class TestSequencePoolSum(OpTest):
    op_type = "sequence_pool"

    def setup(self):
        rng = np.random.RandomState(6)
        flat = rng.rand(7, 3).astype("float32")
        lengths = [3, 4]
        self.inputs = {"X": (flat, lengths)}
        self.attrs = {"pooltype": "SUM"}
        self.outputs = {
            "Out": np.stack([flat[:3].sum(0), flat[3:].sum(0)])
        }

    def test_output(self):
        self.setup()
        self.check_output()

    def test_grad(self):
        self.setup()
        self.check_grad(["X"], "Out")


class TestSigmoidOp(OpTest):
    op_type = "sigmoid"

    def setup(self):
        rng = np.random.RandomState(7)
        x = rng.uniform(-2, 2, (4, 6)).astype("float32")
        self.inputs = {"X": x}
        self.outputs = {"Out": 1.0 / (1.0 + np.exp(-x))}

    def test_output(self):
        self.setup()
        self.check_output()

    def test_grad(self):
        self.setup()
        self.check_grad(["X"], "Out", max_relative_error=0.01)


class TestReduceMeanOp(OpTest):
    op_type = "reduce_mean"

    def setup(self):
        rng = np.random.RandomState(8)
        x = rng.rand(3, 4, 5).astype("float32")
        self.inputs = {"X": x}
        self.attrs = {"dim": [1], "keep_dim": False, "reduce_all": False}
        self.outputs = {"Out": x.mean(axis=1)}

    def test_output(self):
        self.setup()
        self.check_output()

    def test_grad(self):
        self.setup()
        self.check_grad(["X"], "Out")


class TestBatchNormOp(OpTest):
    """batch_norm, the op every ResNet step runs once a convolution: numpy
    reference for all five outputs and a finite-difference gradient, with
    batch statistics (train) and moving statistics (is_test), channels
    second (NCHW) and last (NHWC)."""

    op_type = "batch_norm"

    def setup(self, is_test, layout):
        rng = np.random.RandomState(9)
        N, C, H = 3, 4, 5
        shape = (N, C, H, H) if layout == "NCHW" else (N, H, H, C)
        caxis = 1 if layout == "NCHW" else 3
        axes = tuple(i for i in range(4) if i != caxis)
        bshape = [1, 1, 1, 1]
        bshape[caxis] = C
        x = rng.uniform(-1, 1, shape).astype("float32")
        scale = rng.uniform(0.6, 1.4, (C,)).astype("float32")
        bias = rng.uniform(-0.2, 0.2, (C,)).astype("float32")
        # nonzero moving stats: an all-zero mean would let a wrong
        # momentum blend of the old mean pass undetected
        mean = rng.uniform(-0.5, 0.5, (C,)).astype("float32")
        var = rng.uniform(0.5, 1.5, (C,)).astype("float32")
        eps, momentum = 1e-5, 0.9
        if is_test:
            use_mean, use_var, new_mean, new_var = mean, var, mean, var
        else:
            use_mean, use_var = x.mean(axis=axes), x.var(axis=axes)
            new_mean = momentum * mean + (1 - momentum) * use_mean
            new_var = momentum * var + (1 - momentum) * use_var
        inv = 1.0 / np.sqrt(use_var + eps)
        y = ((x - use_mean.reshape(bshape)) * inv.reshape(bshape)
             * scale.reshape(bshape) + bias.reshape(bshape))
        self.inputs = {"X": x, "Scale": scale, "Bias": bias,
                       "Mean": mean, "Variance": var}
        self.attrs = {"epsilon": eps, "momentum": momentum,
                      "is_test": is_test, "data_layout": layout}
        self.outputs = {"Y": y, "MeanOut": new_mean, "VarianceOut": new_var,
                        "SavedMean": use_mean, "SavedVariance": inv}

    @pytest.mark.parametrize("layout", ["NCHW", "NHWC"])
    @pytest.mark.parametrize("is_test", [False, True])
    def test_output(self, is_test, layout):
        self.setup(is_test, layout)
        self.check_output(atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("layout", ["NCHW", "NHWC"])
    @pytest.mark.parametrize("is_test", [False, True])
    def test_grad(self, is_test, layout):
        self.setup(is_test, layout)
        self.check_grad(["X", "Scale", "Bias"], "Y",
                        max_relative_error=0.02)
