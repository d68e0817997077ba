"""The correctness comparison, on the CPU at a size a test run can hold:
a sound run comes out correct, and with the timed path broken underneath
(one fault at a time, planted in the program) it comes out not correct.
The harness's look for a chip is skipped; everything else is a run.

Faults: a round or decode step that leaves its state unchanged; half of
each batch left out, the mean taken over the rest; an answer (the landed
factors) or a served token altered where it is produced; the global's
components of the levels no client reaches dropped; a request served on
another tenant's adapter. The exchange between chips does not exist in
these one-chip cells.

``test_control_*`` read the control (the reference one precision step
below the configuration's, put in the program's place) at a small size:
it must read further from the reference than the program. Whether it
passes a cell's limit is read on the chip at the cell's size
(``bench/calibrate.py``).
"""
import time

from harness.cell import run_cell

ROUNDS = "fl-vitb-local-train"
SERVE = "serve-granite-chat-knee"
SMALL_VIT = {
    "config": {"hidden_size": 64, "num_hidden_layers": 2,
               "num_attention_heads": 4, "intermediate_size": 128,
               "tokens_per_item": 8, "num_labels": 10,
               "program": {"arch": "vit-base", "remat": True, "block_q": 8,
                           "block_kv": 8}},
    "traffic": {"num_clients": 6, "clients_per_round": 3, "batch_size": 4,
                "items_per_client": [8, 12], "labels_per_client": 3}}
SMALL_GRANITE = {
    "config": {"hidden_size": 64, "num_hidden_layers": 2,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "intermediate_size": 128, "vocab_size": 97},
    "traffic": {"slots": 4, "prompt_len": 16, "output_median": 8,
                "output_min": 2, "output_max": 16, "tenants": 5,
                "rate_per_s": 6.0, "sample_tokens": 40}}
TENANTS = SMALL_GRANITE["traffic"]["tenants"]


def run(workload, small, seed=2 ** 33 + 5, mode="run", keep=None):
    return run_cell(workload, seed, 2.0, False, t_start=time.perf_counter(),
                    require_tpu=False, overrides=small, mode=mode, keep=keep)


def failed_checks(res):
    return [k for k, c in res["checks"].items() if c["value"] > c["limit"]]


def test_rounds_sound():
    res = run(ROUNDS, SMALL_VIT)
    assert res["correct"], res["checks"]


def test_rounds_state_unchanged(monkeypatch):
    from repro.federation import server
    monkeypatch.setattr(server.FederatedLoRA, "_write_factors",
                        lambda self, results: None)
    res = run(ROUNDS, SMALL_VIT)
    assert not res["correct"] and "change_gap" in failed_checks(res)


def test_rounds_half_batch(monkeypatch):
    import jax
    from repro.models import transformer
    orig = transformer.Model.train_loss

    def half(self, params, batch, **kw):
        b = jax.tree.leaves(batch)[0].shape[0]
        return orig(self, params, jax.tree.map(lambda x: x[:b // 2], batch),
                    **kw)

    monkeypatch.setattr(transformer.Model, "train_loss", half)
    res = run(ROUNDS, SMALL_VIT)
    assert not res["correct"], res["checks"]


def test_rounds_answer_altered(monkeypatch):
    import jax
    from repro.federation import server
    orig = server.FederatedLoRA._write_factors

    def altered(self, results):
        orig(self, results)
        self.global_lora = jax.tree_util.tree_map_with_path(
            lambda p, x: x * 1.25 if getattr(p[-1], "key", "") == "lora_b"
            else x, self.global_lora)

    monkeypatch.setattr(server.FederatedLoRA, "_write_factors", altered)
    res = run(ROUNDS, SMALL_VIT)
    assert not res["correct"] and "change_gap" in failed_checks(res)


def test_rounds_kept_components_dropped(monkeypatch):
    """The levels no sampled client reaches lose the global's components:
    the kind of round the followed first round or the other-kind round
    runs, whichever keeps components."""
    from repro.core import aggregation
    orig = aggregation._grouped_core

    def dropped(group_bs, group_as, warg, global_bs, global_as, fallback,
                **kw):
        if fallback is not None:
            global_bs = tuple(b * 0 for b in global_bs)
        return orig(group_bs, group_as, warg, global_bs, global_as,
                    fallback, **kw)

    monkeypatch.setattr(aggregation, "_grouped_core", dropped)
    res = run(ROUNDS, SMALL_VIT)
    assert not res["correct"] and "change_gap" in failed_checks(res)


def test_serve_sound():
    res = run(SERVE, SMALL_GRANITE)
    assert res["correct"], res["checks"]


def test_serve_token_altered(monkeypatch):
    from repro.serving import engine as eng
    orig = eng.ServingEngine.decode
    calls = []

    def altered(self, active_mask):
        out = orig(self, active_mask)
        calls.append(1)
        if len(calls) % 5 == 0:       # every fifth step: slot 0's token + 1
            return out.at[0].set((out[0] + 1) % self.model.cfg.vocab_size)
        return out

    monkeypatch.setattr(eng.ServingEngine, "decode", altered)
    res = run(SERVE, SMALL_GRANITE)
    assert not res["correct"] and "served_gap" in failed_checks(res)


def test_serve_state_unchanged(monkeypatch):
    from repro.serving import engine as eng
    orig = eng.ServingEngine.decode

    def stale(self, active_mask):
        cache = self.cache
        out = orig(self, active_mask)
        self.cache = cache            # the step's cache write is lost
        return out

    monkeypatch.setattr(eng.ServingEngine, "decode", stale)
    res = run(SERVE, SMALL_GRANITE)
    assert not res["correct"] and "logit_err" in failed_checks(res)


def test_serve_wrong_adapter(monkeypatch):
    from repro.serving import engine as eng
    orig = eng.ServingEngine.admit

    def misrouted(self, slot_idx, prompts, adapter_ids):
        ids = [f"tenant{(int(a[len('tenant'):]) + 1) % TENANTS}"
               for a in adapter_ids]
        return orig(self, slot_idx, prompts, ids)

    monkeypatch.setattr(eng.ServingEngine, "admit", misrouted)
    res = run(SERVE, SMALL_GRANITE)
    assert not res["correct"] and "logit_err" in failed_checks(res)


SMALL_GRANITE_WIDE = {
    "config": {"hidden_size": 256, "num_hidden_layers": 2,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "intermediate_size": 512, "vocab_size": 4096},
    "traffic": {"slots": 4, "prompt_len": 32, "output_median": 24,
                "output_min": 8, "output_max": 48, "tenants": 5,
                "rate_per_s": 4.0, "sample_tokens": 150}}


def test_control_rounds():
    """The control (the reference at ``high``) reads the compared loss gap
    further from the reference than the program does, and the half-batch
    fault reads both compared numbers far further. (On the chip, at the
    cell's size, the control also fails ``change_gap``: PERF.md.)"""
    keep = {}
    run(ROUNDS, SMALL_VIT, seed=1, mode="calibrate", keep=keep)
    r = keep["record"]["readings"]

    def compared(key):          # the first round and the other kind's
        return max(r[key][0], r[key][-1])

    assert compared("control_loss_gaps") > compared("loss_gaps")
    assert compared("half_batch_loss_gaps") > 100 * compared("loss_gaps")
    assert compared("half_batch_change_gaps") > 100 * compared("change_gaps")


def test_control_serve():
    """The control (the reference computed in int8) reads its logits
    further from the reference than the engine's, and so do the faults the
    reference stands in for."""
    keep = {}
    run(SERVE, SMALL_GRANITE_WIDE, seed=1, mode="calibrate", keep=keep)
    r = keep["record"]["readings"]
    assert r["control_logit_err"] > r["logit_err"]
    assert r["wrong_adapter_logit_err"] > r["logit_err"]
    assert r["no_adapter_logit_err"] > r["logit_err"]
