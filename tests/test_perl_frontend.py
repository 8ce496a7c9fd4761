"""Perl frontend over the C ABI (perl-package/): proves the binding
surface is sufficient for a non-Python frontend — the reference's
R-package story (R code over .Call stubs into c_api.cc). The test
trains + checkpoints a model in Python, then a Perl script loads the
checkpoint, runs inference, and performs one SGD step; outputs and the
post-step loss drop are validated against Python."""
import os
import shutil
import subprocess

import numpy as np
import pytest

import mxnet_tpu as mx

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _build():
    if not shutil.which("perl") or not shutil.which("xsubpp"):
        pytest.skip("no perl/xsubpp toolchain")
    r = subprocess.run(["make", "-C", REPO, "perl"], capture_output=True,
                       text=True)
    if r.returncode != 0:
        pytest.skip("perl extension build failed: %s" % r.stderr[-500:])


def test_perl_loads_checkpoint_infers_and_trains(tmp_path):
    _build()

    # train a small net in Python and checkpoint it
    rng = np.random.RandomState(3)
    X = rng.randn(32, 6).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=8, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    it = mx.io.NDArrayIter(X, y, batch_size=32, label_name="softmax_label")
    model = mx.model.FeedForward(net, num_epoch=3, learning_rate=0.1,
                                 numpy_batch_size=32)
    model.fit(it)
    prefix = str(tmp_path / "m")
    model.save(prefix, 3)

    np.savetxt(tmp_path / "d.csv", X, delimiter=",")
    np.savetxt(tmp_path / "l.csv", y, delimiter=",")

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        ["perl", os.path.join(REPO, "perl-package", "examples",
                              "train_step.pl"),
         prefix + "-symbol.json", "%s-%04d.params" % (prefix, 3),
         str(tmp_path / "d.csv"), str(tmp_path / "l.csv"), "0.001"],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, (r.stdout, r.stderr)
    out = dict(line.split("=", 1) for line in r.stdout.strip().splitlines())

    # inference agrees with Python
    probs_perl = np.array([float(v) for v in out["probs"].split(",")])
    pred = model.predict(mx.io.NDArrayIter(X, batch_size=32))
    np.testing.assert_allclose(probs_perl, pred.ravel()[:6], rtol=1e-4,
                               atol=1e-5)

    # the Perl-side SGD step reduced the loss
    assert float(out["loss_after"]) < float(out["loss_before"])


def test_perl_error_path(tmp_path):
    _build()
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        ["perl", "-I", os.path.join(REPO, "perl-package", "lib"),
         "-I", os.path.join(REPO, "perl-package", "blib"),
         "-MMXNetTPU",
         "-e", 'MXNetTPU::Symbol->load_json("{bad"); print "no\\n"'],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode != 0
    assert "MXSymbolCreateFromJSON failed" in r.stderr


def test_perl_round2_surface(tmp_path):
    """The round-2 XS functions: symbol save/load-from-file, grad,
    optimizer create/update (momentum math checked numerically),
    random_seed, and the odd-kv-count croak."""
    _build()
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=2, no_bias=True, name="fc")
    json_path = tmp_path / "net.json"
    script = tmp_path / "round2.pl"
    script.write_text(r"""
use strict; use warnings;
use lib "%(lib)s", "%(blib)s"; use MXNetTPU;
MXNetTPU::random_seed(11);

my $sym = MXNetTPU::Symbol->load_json(do {
    local $/; open my $fh, '<', $ARGV[0] or die; <$fh> });
$sym->save("%(tmp)s/resaved.json");
my $back = MXNetTPU::Symbol->load("%(tmp)s/resaved.json");
print "args=", join(",", $back->list_arguments), "\n";

my $g = $sym->grad("fc_weight");
print "gargs=", join(",", $g->list_arguments), "\n";

# optimizer: sgd with momentum on a 4-element weight, grad all 0.5
my $w = MXNetTPU::NDArray->from_list([1, 1, 1, 1]);
my $grad = MXNetTPU::NDArray->from_list([0.5, 0.5, 0.5, 0.5]);
my $opt = MXNetTPU::Optimizer->create("sgd", momentum => "0.9");
$opt->update(0, $w->{handle}, $grad->{handle}, 0.1, 0.0);
$opt->update(0, $w->{handle}, $grad->{handle}, 0.1, 0.0);
print "w=", join(",", $w->values), "\n";

my $died = eval { MXNetTPU::optimizer_create("sgd", "momentum"); 1 } ? 0 : 1;
print "odd_kv_croaks=$died\n";
""" % {"lib": os.path.join(REPO, "perl-package", "lib"),
       "blib": os.path.join(REPO, "perl-package", "blib"),
       "tmp": str(tmp_path)})
    json_path.write_text(net.tojson())

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(["perl", str(script), str(json_path)],
                       capture_output=True, text=True, env=env,
                       timeout=300)
    assert r.returncode == 0, (r.stdout, r.stderr)
    out = dict(line.split("=", 1)
               for line in r.stdout.strip().splitlines())
    assert out["args"] == "data,fc_weight"
    assert out["gargs"] == "data,fc_weight"
    # two momentum-SGD steps: w1 = 1 - .05; mom2 = .9*(-.05) - .05
    np.testing.assert_allclose(
        [float(v) for v in out["w"].split(",")],
        np.full(4, 1.0 - 0.05 + (0.9 * -0.05 - 0.05)), rtol=1e-5)
    assert out["odd_kv_croaks"] == "1"
