"""A configuration chooses its plain reference module by name: a new
architecture arrives as a file beside the harness, with no edit to it."""
import time

import pytest

import bench
from bench import harness as H
from bench import reference as R
from bench.tests.tiny import tiny_files

SEED = 2**31 + 29

#: a reference module of another family: it hands the work to the
#: Llama/Qwen3 module and records what the harness asked of it
STUB = '''
from bench import reference as R

CALLS = []
Model = R.Model


def run_reference(*args, **kw):
    CALLS.append("run_reference")
    return R.run_reference(*args, **kw)


def check_program(conf, cfg):
    CALLS.append("check_program")
    return {} if conf.get("stub_agrees", True) else {"hidden_size": (1, 2)}


def flops_per_token(conf, seq_len):
    CALLS.append("flops_per_token")
    return 1234.5


def param_count(conf):
    return R.param_count(conf)
'''


@pytest.fixture
def stub(tmp_path, monkeypatch):
    (tmp_path / "reference_stubfamily.py").write_text(STUB)
    monkeypatch.setattr(bench, "__path__", [*bench.__path__, str(tmp_path)])
    yield
    import sys
    sys.modules.pop("bench.reference_stubfamily", None)


def test_stub_reference_module_is_taken_checked_and_counted(stub):
    files = tiny_files("smollm-135m.chip1.local")
    files["config"]["reference"] = "reference_stubfamily"
    mod = H.reference_module(files["config"])
    r = H.run_cell(files, SEED, 0.5, False, time.perf_counter(),
                   H.device_info(1))
    assert r["correct"], r["compared"]
    assert {"check_program", "run_reference",
            "flops_per_token"} <= set(mod.CALLS)
    files["config"]["stub_agrees"] = False
    with pytest.raises(SystemExit):
        H.model_config(files["config"])


def _palm_flops(conf, seq_len):
    """Model FLOPs a token as the harness counted them before reference
    modules chose: 6 N + 12 L H Q T over one dense SwiGLU MLP a layer."""
    d, f, L = (conf["hidden_size"], conf["intermediate_size"],
               conf["num_hidden_layers"])
    h, kvh, v = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["vocab_size"])
    hd = conf.get("head_dim") or d // h
    n = L * (d * h * hd * 2 + d * kvh * hd * 2 + 3 * d * f) + v * d
    return 6.0 * n + 12.0 * L * h * hd * seq_len


@pytest.mark.parametrize("name,seq_len", [("smollm-135m", 2048),
                                          ("qwen3-0.6b", 4096)])
def test_missing_reference_key_is_the_llama_family(name, seq_len):
    conf = H.load_json(H.BENCH / "configs" / f"{name}.json")
    assert "reference" not in conf
    assert H.reference_module(conf) is R
    assert H.reference_module(conf).flops_per_token(conf, seq_len) == \
        _palm_flops(conf, seq_len)
    assert H.model_config(conf).d_model == conf["hidden_size"]
