"""What decides `correct`, driven end to end on the CPU at a tiny size:
the harness's run (without its look for a chip) comes out correct on the
program as it is, and not correct with the timed path broken underneath
(a decoded token altered where the engine hands it out; a decode step
that returns its state unchanged) or with the fp8 control in the
program's place."""

import pytest

from conftest import make_tiny_root
from harness import cell
from harness.cell import Session
from harness.manifest import Manifest

SEED = 2 ** 32 + 77


@pytest.fixture(scope="module")
def man(tmp_path_factory):
    return Manifest(make_tiny_root(tmp_path_factory.mktemp("tiny")))


def run(man, **kw):
    return cell.run(man, "tiny.mix", SEED, 2.0, False, 0.0,
                    compile_cache=False, **kw)


def test_sound_run_is_correct(man):
    out = run(man)
    assert out["correct"] is True
    assert out["check"]["tokens_checked"]["value"] >= \
        out["check"]["tokens_checked"]["limit"]
    assert list(out)[-1] == "check"
    assert {"tokens_per_s", "itl_p95_ms", "setup_s"} \
        <= set(out["metrics"])


def test_altered_token_is_not_correct(man):
    out = run(man, alter=lambda slot, tok: (tok + 1) % 256)
    assert out["correct"] is False
    assert out["check"]["logit_gap"]["value"] > \
        out["check"]["logit_gap"]["limit"]


def _unchanged_state():
    from repro.serve import PagedContinuousBatchingEngine

    class Frozen(PagedContinuousBatchingEngine):
        """Decode steps that hand out their tokens but return the decode
        state unchanged (KV, positions and last tokens)."""

        def decode_once(self):
            before = self.state
            toks = super().decode_once()
            if toks is not None:
                self.state = before
            return toks
    return Frozen


def test_unchanged_state_is_not_correct(man):
    out = run(man, engine_base=_unchanged_state())
    assert out["correct"] is False


def test_fp8_control_is_not_correct(man):
    """The control — the reference in fp8 in the program's place — reads
    beyond the limits on the same prompts and tokens, while the program
    reads within them."""
    mix = man.traffic("tiny-mix")
    ses = Session(man, "tiny.mix", SEED, compile_cache=False,
                  mix=dict(mix, check=dict(mix["check"], sample_tokens=256,
                                           max_sequences=8)))
    s = ses.serve(ses.plan(SEED), 3.0)
    reqs, logits = s["driver"].reqs, ses.engine.logits
    ses.free_engine()
    read = ses.check(reqs, logits, control=True)
    lim = mix["check"]
    assert read["logit_gap"] <= lim["logit_gap_limit"]
    assert read["logit_rel"] <= lim["logit_rel_limit"]
    assert (read["control_gap"] > lim["logit_gap_limit"]
            or read["control_rel"] > lim["logit_rel_limit"])
    assert read["control_rel"] > 100 * read["logit_rel"]
