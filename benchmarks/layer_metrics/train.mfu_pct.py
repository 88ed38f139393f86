"""Model FLOP/s utilisation: tokens/s/chip (untraced part of the window)
times the benchmark's own 6N + 6Lds over the chip's bf16 peak."""
from benchmarks import peaks


def read(facts):
    rate = (facts.get("end_to_end") or {}).get("train_tok_s_chip")
    if rate is None or facts["device"]["platform"] != "tpu":
        return None         # a utilisation of the chip's peak, or nothing
    cfg = facts["config"]
    per_token = peaks.gpt2_train_flops_per_token(
        int(cfg["n_layer"]), int(cfg["n_embd"]), int(cfg["vocab_size"]),
        int(facts["traffic"]["seq"]))
    peak = peaks.peaks_for(facts["device"]["kind"])["flops_per_s"]
    return 100.0 * rate * per_token / peak
