"""Operation and byte counts against hand-worked cases."""

from fedbench import roofline, spec


def test_fedavg_counts():
    # 225 clients of the mlp: read 225 x 25,450 f32 and 225 weights, write
    # 25,450 values; a multiply and an add per element.
    assert roofline.fedavg(225, 25_450) == (
        11_452_500, 4 * 225 * 25_450 + 4 * 225 + 4 * 25_450)
    assert roofline.fedavg(225, 25_450)[1] == 23_007_700
    assert roofline.fedavg(16, 1_663_370)[1] == 113_109_224


def test_decode_counts():
    # 225 payloads of topk(0.01)|int8(1024) over 25,450 params: one block
    # of 1,024 codes holds the 254 kept values; the scatter writes the
    # dense row.
    stages = [("int8", {"blocks": 1, "block": 1024, "n": 254}),
              ("topk", {"k": 254, "n": 25_450})]
    flops, nbytes = roofline.decode(225, stages)
    assert flops == 225 * 1024
    assert nbytes == 225 * (5 * 1024 + 4) + 225 * (8 * 254 + 4 * 25_450)


def test_mlp_flops_per_sample():
    # 784 -> 300 -> 10: the forward pass is 2 x (784*300 + 300*10)
    # operations, and a training sample three forward passes.
    config = spec.load_json("configs", "mnist_mlp_fleet256")
    ref = spec.reference(config["model"])
    assert config["model_args"]["hidden"] == 300
    assert ref.flops_per_sample(config) == 3 * 476_400 == 1_429_200
    assert ref.flops_per_update(config) == 1_429_200 * 4 * 32
    hand = dict(config, model_args=dict(config["model_args"], hidden=32))
    assert ref.flops_per_sample(hand) == 3 * 50_816 == 152_448


def test_least_time_takes_the_bound_that_binds():
    peak = spec.peaks("TPU v5 lite")
    f, b = roofline.fedavg(16, 1_663_370)
    assert roofline.least_seconds(f, b, peak) == b / 819e9
    assert roofline.least_seconds(197e12, 0, peak) == 1.0
