"""The work functions against counts made by hand."""

from bench.work import decode, encode, stencil

N = 160 * 1152 * 1152  # a stream visit's extent: 144 + 2 * 8 planes


def test_stencil_call():
    f32 = [[160, 1152, 1152], "float32"]
    call = {"in": [f32] * 3, "out": [f32] * 2, "kw": {"steps": 2}}
    got = stencil.work(call)
    # three fields read once, two written once, 4 bytes a value
    assert got["bytes"] == 5 * 4 * N == 4246732800
    # 33 operations a point and a step (1 + 4 * 7 for the Laplacian,
    # 4 for the update), two steps
    assert got["ops"] == 33 * 2 * N


def test_encode_and_decode_of_one_unit():
    # a 16-plane unit at 12 planes a value: 64 * 12 bits = 24 words a
    # 4x4x4 block, one int32 exponent a block
    nb = 16 * 1152 * 1152 // 64
    field = [[16, 1152, 1152], "float32"]
    payload = [[24, nb], "uint32"]
    emax = [[nb], "int32"]
    enc = encode.work({"in": [field], "out": [payload, emax], "kw": {}})
    dec = decode.work({"in": [payload, emax], "out": [field], "kw": {}})
    want = 16 * 1152 * 1152 * 4 + nb * 24 * 4 + nb * 4
    assert enc["bytes"] == dec["bytes"] == want == 118112256
    assert enc["ops"] is None and dec["ops"] is None
