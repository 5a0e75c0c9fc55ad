"""End-to-end fault-tolerant training demo (deliverable (b) driver).

Trains a model for a few hundred steps while XID failures are injected at
chosen steps; the runtime classifies each failure (paper Table 3), applies
the retry policy, and resumes from the last two-phase checkpoint.  Compares
the paper-faithful fixed-delay policy against the paper's proposed
XID-branching policy (§4.3.5).

    PYTHONPATH=src python examples/fault_tolerant_training.py
"""
import tempfile

from repro.compile_cache import use_compile_cache
from repro.launch.train import run_training


def main():
    use_compile_cache()
    for policy in ("fixed", "xid_branch"):
        print(f"\n=== policy: {policy} ===")
        # fresh checkpoint dir per run: restoring a stale step-60
        # checkpoint from a previous invocation would skip the retries
        # this demo exists to show
        with tempfile.TemporaryDirectory(
                prefix=f"repro_ft_{policy}_") as ckpt_dir:
            rep = run_training(
                "stablelm-3b", steps=60, batch=2, seq=64,
                fail_at=(22, 41), fail_xid=94, retry_policy=policy,
                ckpt_dir=ckpt_dir, log_every=20)
        print(f"steps={rep.steps_done} failures={rep.n_failures} "
              f"restarts={rep.n_restarts} saves={rep.checkpoint_saves} "
              f"final_loss={rep.final_loss:.4f} "
              f"tokens/s={rep.tokens_per_s:,.0f}")
        assert rep.steps_done == 60 and rep.n_restarts == 2


if __name__ == "__main__":
    main()
