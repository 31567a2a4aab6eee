"""Quickstart: the whole Peregrine loop in ~40 lines.

Synthesises a Mirai-style trace, trains the detector on the benign prefix,
then streams the attack window through the data-plane feature pipeline and
scores per-epoch records — §3.2's workflow end to end.  The service's
``observe_stream``/``process_stream`` chunk the trace with bounded memory
and carry flow-table state plus the global packet count across chunks.

  PYTHONPATH=src python examples/quickstart.py

Swap the FC data plane by name, e.g. the hash-partitioned flow tables:
``DetectionService(..., backend="sharded", shards=16)`` — and the MD
scoring stage the same way: ``DetectionService(..., md_backend="pallas")``
runs KitNET's ensemble layer through the fused Pallas kernel, with each
chunk's records scored as they arrive (per-chunk streaming scores are
bit-identical to one-batch for the serial-semantics FC backends).
"""
from repro.detection.metrics import auc
from repro.serving import DetectionService
from repro.traffic import synth_trace
from repro.launch.cache import enable_compile_cache

enable_compile_cache()

# 1. a trace: benign training prefix + eval window with the attack mixed in
data = synth_trace("mirai", n_train=12000, n_benign_eval=6000,
                   n_attack=6000, seed=0)

# 2. the detector: per-packet FC in the (TPU) data plane, one feature record
#    every 256 packets to the KitNET classifier — sampling AFTER features.
svc = DetectionService(epoch=256, n_slots=8192, mode="exact")

# 3. training phase: benign traffic only (first 1M packets in the paper)
svc.observe_stream(data["train"], chunk=4096)
svc.fit(fpr=0.01)
print(f"trained; alarm threshold RMSE={svc.threshold:.4f}")

# 4. detection phase: stream the eval window. Record indices are global
#    stream positions, so subtract the eval window's start offset to look up
#    labels — chunking does not change which packets close an epoch.
eval_start = svc.pkt_count
idx, scores, alarms = svc.process_stream(data["eval"], chunk=4096)
labels = data["eval"]["label"][idx - eval_start]

print(f"{len(scores)} records scored, {int(alarms.sum())} alarms")
print(f"attack-record AUC = {auc(scores, labels):.3f}  "
      f"(paper: >0.8 for 13/15 attacks)")
