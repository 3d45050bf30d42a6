"""The plain reference of the ChaCha20 guest (`build_chacha20`, export
`chacha20`): RFC 8439's block function and encryption in numpy uint32,
word by word, independent of every engine.

    2.1  QUARTERROUND(a, b, c, d):
             a += b; d ^= a; d <<<= 16;   c += d; b ^= c; b <<<= 12;
             a += b; d ^= a; d <<<= 8;    c += d; b ^= c; b <<<= 7;
    2.3  state = constants | key | counter | nonce    (16 words)
         ten times: QUARTERROUND on the columns (0, 4, 8, 12) ..
                    (3, 7, 11, 15), then on the diagonals
                    (0, 5, 10, 15), (1, 6, 11, 12), (2, 7, 8, 13),
                    (3, 4, 9, 14)
         block = working state + state, word by word, little-endian
    2.4  ciphertext block j = message block j ^ block(key, 1 + j, nonce)

No rows and no shuffles here: the columns and the diagonals are index
tuples, as section 2.3 writes them.  `block_words` reproduces test
vector 2.3.2 (tests/test_chacha20_config.py).

What the configuration set itself (`assumed`), the same as the guest:
- the seed's recurrence: w' = w * 1664525 + 1013904223 mod 2**32 from
  w = seed gives the eight key words, then the three nonce words; the
  next value, in all four lanes, times (0x9E3779B1, 0x85EBCA6B,
  0xC2B2AE35, 0x27D4EB2F) plus (1, 2, 3, 4) is the message's first four
  words, and every next four are the same step applied to each word;
- the fold: acc = rotl(acc, 1) ^ (64 bits of ciphertext, little-endian)
  over the whole ciphertext, in place of writing it out.

Vectorised over lanes (they share nothing) and over a lane's blocks
(each block's key stream depends on its counter alone); a word's
arithmetic is the scalar loop's, operation for operation.  Lanes go
through in chunks of 64, so the working set is some 40 MB whatever the
lane count and an array of a word stays in the cache.

Results are the raw 64-bit cells a wasm i64 result occupies.
`reference(func, args)` answers one lane at 3,072 blocks,
`reference_lanes` all lanes in one call at the size it is given.
"""

import numpy as np

BLOCKS = 3072
SIGMA = np.frombuffer(b"expand 32-byte k", "<u4")
LCG_MUL, LCG_ADD = np.uint32(1664525), np.uint32(1013904223)
MSG_MUL = np.array([0x9E3779B1, 0x85EBCA6B, 0xC2B2AE35, 0x27D4EB2F],
                   np.uint32)
MSG_ADD = np.array([1, 2, 3, 4], np.uint32)
COLUMNS = ((0, 4, 8, 12), (1, 5, 9, 13), (2, 6, 10, 14), (3, 7, 11, 15))
DIAGONALS = ((0, 5, 10, 15), (1, 6, 11, 12), (2, 7, 8, 13), (3, 4, 9, 14))
LANE_CHUNK = 64


def rotl32(x, n):
    return (x << np.uint32(n)) | (x >> np.uint32(32 - n))


def quarter_round(x, a, b, c, d):
    """Section 2.1 on the words a, b, c, d of the list `x` (arrays of
    its own, updated in place)."""
    x[a] += x[b]
    x[d] ^= x[a]
    x[d] = rotl32(x[d], 16)
    x[c] += x[d]
    x[b] ^= x[c]
    x[b] = rotl32(x[b], 12)
    x[a] += x[b]
    x[d] ^= x[a]
    x[d] = rotl32(x[d], 8)
    x[c] += x[d]
    x[b] ^= x[c]
    x[b] = rotl32(x[b], 7)


def block_words(key, counter, nonce):
    """Section 2.3: the sixteen words of the key-stream block, each an
    array of the shape `key[i]`, `counter` and `nonce[i]` broadcast to
    (uint32; a block's serialisation is these words, little-endian)."""
    state = [np.uint32(w) for w in SIGMA] + list(key) + [counter] \
        + list(nonce)
    shape = np.broadcast(*state).shape
    state = [np.broadcast_to(np.asarray(w, np.uint32), shape)
             for w in state]
    x = [w.copy() for w in state]
    for _ in range(10):
        for index in COLUMNS + DIAGONALS:
            quarter_round(x, *index)
    return [xi + si for xi, si in zip(x, state)]


def derive(seeds, blocks):
    """-> (key [8], nonce [3], message uint32[lanes, blocks * 16]) from
    the lanes' seeds, by the recurrence above."""
    w = np.asarray(seeds).astype(np.uint32)
    words = []
    for _ in range(12):
        w = w * LCG_MUL + LCG_ADD
        words.append(w)
    m = words[11][:, None] * MSG_MUL + MSG_ADD          # [lanes, 4]
    message = np.empty((len(w), blocks * 4, 4), np.uint32)
    for j in range(blocks * 4):
        message[:, j] = m
        m = m * LCG_MUL + LCG_ADD
    return words[:8], words[8:11], message.reshape(len(w), -1)


def encrypt(seeds, blocks=BLOCKS):
    """Section 2.4, initial counter 1: the ciphertext of every lane's
    message as uint32[lanes, blocks * 16]."""
    key, nonce, message = derive(seeds, blocks)
    counter = np.arange(1, blocks + 1, dtype=np.uint32)[None, :]
    stream = block_words([k[:, None] for k in key], counter,
                         [n[:, None] for n in nonce])
    text = message.reshape(len(message), blocks, 16)
    for i, word in enumerate(stream):   # word i of every block
        text[:, :, i] ^= word
    return message


def fold(words):
    """acc = rotl(acc, 1) ^ doubleword over each lane's uint32 words,
    two a little-endian doubleword: uint64[lanes]."""
    acc = np.zeros(words.shape[0], np.uint64)
    one, back = np.uint64(1), np.uint64(63)
    for bits in np.ascontiguousarray(words).view("<u8").T:
        acc = ((acc << one) | (acc >> back)) ^ bits
    return acc


def reference_lanes(func, lane_args, blocks=BLOCKS):
    """Every lane's raw result cell in one call: uint64[lanes]."""
    if func != "chacha20":
        raise KeyError(func)
    seeds = np.asarray(lane_args, np.int64)
    with np.errstate(over="ignore"):
        return np.concatenate([
            fold(encrypt(seeds[i:i + LANE_CHUNK], blocks))
            for i in range(0, len(seeds), LANE_CHUNK)])


def reference(func, args):
    return [int(reference_lanes(func, [int(args[0])])[0])]
