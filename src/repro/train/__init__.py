"""The fused LDA iteration pipeline (lda_step.py: donated single-dispatch
step, incremental delta count updates, sync-free scanned training
stretches, streamed and disk-paged epochs)."""
