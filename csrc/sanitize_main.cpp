// Sanitizer driver for host.cpp: exercises every extern-C entry point with
// boundary-shaped inputs under ASan/UBSan (`make sanitize`). Not a value
// test — tests/test_native.py pins the semantics against the Python
// reference implementations; this exists so an out-of-bounds index or UB
// in the byte-wrangling (the GB-scale tile loops especially) dies loudly
// in CI instead of corrupting a weight load.

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <vector>

extern "C" {
uint64_t xorshift_fill_f32(uint64_t state, float* out, int64_t n,
                           double divisor);
void q40_decode(const uint8_t* in, float* out, int64_t nb);
void q40_encode(const float* in, uint8_t* out, int64_t nb);
void q80_decode(const uint8_t* in, float* out, int64_t nb);
void q80_encode(const float* in, uint8_t* out, int64_t nb);
void q40_tile_kernel_layout(const uint8_t* qs, const uint16_t* d16,
                            uint8_t* qs_t, float* scale, int64_t n_stacked,
                            int64_t d, int64_t nb, int32_t n_threads);
void q40_tile_kernel_layout_nb(const uint8_t* qs, const uint16_t* d16,
                               uint8_t* qs_t, float* scale,
                               int64_t n_stacked, int64_t d, int64_t nb,
                               int32_t n_threads);
void* tok_create(const uint8_t* blob, const int64_t* offsets,
                 const float* scores, int32_t n);
void tok_destroy(void* handle);
int64_t tok_encode(void* handle, const uint8_t* text, int64_t len,
                   int32_t* out);
int32_t sample_logits(const float* logits, int32_t n, float temperature,
                      float topp, float coin);
}

int main() {
    // codecs: encode/decode round trips over a seeded stream, including
    // the nb=0 and single-block edges
    const int64_t nb = 37;  // odd block count: no alignment accidents hide
    std::vector<float> vals(nb * 32), back(nb * 32);
    uint64_t st = xorshift_fill_f32(0x123456789abcdefULL, vals.data(),
                                    nb * 32, 1.0);
    std::vector<uint8_t> wire40(nb * 18), wire80(nb * 34);
    q40_encode(vals.data(), wire40.data(), nb);
    q40_decode(wire40.data(), back.data(), nb);
    q80_encode(vals.data(), wire80.data(), nb);
    q80_decode(wire80.data(), back.data(), nb);
    q40_encode(vals.data(), wire40.data(), 0);  // empty input: no touch
    q40_decode(wire40.data(), back.data(), 0);

    // tile re-layout: more threads than work, and a 1x1 plane edge
    const int64_t ns = 3, d = 8, tnb = 4;
    std::vector<uint8_t> qs(ns * d * tnb * 16), qs_t(qs.size());
    std::vector<uint16_t> d16(ns * d * tnb, 0x3c00 /* f16 1.0 */);
    std::vector<float> scale(ns * d * tnb);
    st = xorshift_fill_f32(st, vals.data(), 1, 1.0);
    q40_tile_kernel_layout(qs.data(), d16.data(), qs_t.data(), scale.data(),
                           ns, d, tnb, 64 /* > work: clamps */);
    q40_tile_kernel_layout(qs.data(), d16.data(), qs_t.data(), scale.data(),
                           1, 1, 1, 1);
    // nb-major sibling: a row band cut short (d = 8 < 128) and a block
    // tile cut short (nb = 4 < 8), then more than one band with a ragged
    // last one (d = 200) and a ragged block tile (nb = 11)
    q40_tile_kernel_layout_nb(qs.data(), d16.data(), qs_t.data(),
                              scale.data(), ns, d, tnb, 64);
    q40_tile_kernel_layout_nb(qs.data(), d16.data(), qs_t.data(),
                              scale.data(), 1, 1, 1, 1);
    const int64_t rd = 200, rnb = 11;
    std::vector<uint8_t> rqs(2 * rd * rnb * 16), rqs_t(rqs.size());
    std::vector<uint16_t> rd16(2 * rd * rnb, 0x3c00);
    std::vector<float> rscale(2 * rd * rnb);
    q40_tile_kernel_layout_nb(rqs.data(), rd16.data(), rqs_t.data(),
                              rscale.data(), 2, rd, rnb, 3);

    // tokenizer: multi-byte UTF-8, byte fallback, and merge pressure
    const char* pieces[] = {"a", "b", "ab", "\xc3\xa9"};
    std::vector<uint8_t> blob;
    std::vector<int64_t> offsets = {0};
    std::vector<float> scores;
    for (int i = 0; i < 4; i++) {
        const char* p = pieces[i];
        blob.insert(blob.end(), p, p + std::strlen(p));
        offsets.push_back((int64_t)blob.size());
        scores.push_back((float)i);
    }
    void* tok = tok_create(blob.data(), offsets.data(), scores.data(), 4);
    const char* text = "ab\xc3\xa9zab";  // known pieces + fallback bytes
    std::vector<int32_t> ids(std::strlen(text));
    int64_t n_tok = tok_encode(tok, (const uint8_t*)text,
                               (int64_t)std::strlen(text), ids.data());
    tok_destroy(tok);

    // sampler: argmax, nucleus (degenerate and normal), multinomial tails
    std::vector<float> logits = {0.1f, 2.0f, -1.0f, 0.5f};
    int32_t s0 = sample_logits(logits.data(), 4, 0.0f, 0.9f, 0.5f);
    int32_t s1 = sample_logits(logits.data(), 4, 0.8f, 0.9f, 0.999f);
    int32_t s2 = sample_logits(logits.data(), 4, 0.8f, 0.0f, 0.999f);
    int32_t s3 = sample_logits(logits.data(), 1, 1.0f, 0.5f, 0.0f);

    std::printf("sanitize ok: %lld tokens, samples %d/%d/%d/%d\n",
                (long long)n_tok, s0, s1, s2, s3);
    return (n_tok > 0 && s0 == 1) ? 0 : 1;
}
