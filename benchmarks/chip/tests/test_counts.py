"""The counts of ``counts/`` against numbers worked by hand."""

import harness

# h2o-danube-1.8b, batch 8, 1000 live positions:
#   per layer 2·2560·32·80 + 2·2560·8·80 + 3·2560·6912 = 69,468,160 weights
#   matmul weights 24·69,468,160 + 2560·32000 = 1,749,155,840
#   flops 2·8·1,749,155,840 + 4·8·1000·80·32·24 = 29,952,573,440
#   bytes (1,749,155,840 + 8·2560 + 49·2560)·2 + 8·1000·61,440 = 3,990,123,520
DANUBE = (29_952_573_440, 3_990_123_520)

# mamba2-370m, batch 8 (the state does not grow with the length):
#   projections per layer 1024·(2·2048 + 2·128 + 32) + 2048·1024 = 6,586,368
#   flops 2·8·(48·6,586,368 + 1024·50288) + 8·48·(2·4·2304 + 5·32·64·128)
#       = 6,392,643,584
#   weights (48·(6,586,368 + 14,688) + 1024·50288 + 8·1024 + 1024)·2 = 736,709,632
#   state 48·(32·64·128 + 3·2304)·4 = 51,658,752, read and written per sequence
#   bytes 736,709,632 + 2·8·51,658,752 = 1,563,249,664
MAMBA = (6_392_643_584, 1_563_249_664)


def test_danube_step():
    cell = harness.read_cell("h2o-danube-1.8b.chat")
    assert cell.counts.serve_step(cell.sizes, 8, 1000) == DANUBE


def test_mamba_step():
    cell = harness.read_cell("mamba2-370m.fleet")
    assert cell.counts.serve_step(cell.sizes, 8, 1000) == MAMBA
    assert cell.counts.serve_step(cell.sizes, 8, 7) == MAMBA
