// Text-classification prep — a batch's feature hashing and pair packing,
// one pass each.
//
// The text launch (predictionio_tpu/models/textclassification.py) turns a
// batch's tokens into FNV-1a 64 buckets and the buckets into the distinct
// (title, bucket, count) triples its step gathers. In numpy that is some
// thirty calls over arrays of hundreds of elements, each of which hands the
// interpreter lock back; a busy server's launching thread then waits a
// millisecond or more to win it again. These two loops do the same work in
// tens of microseconds, and the caller keeps the lock across them.

#include <algorithm>
#include <cstdint>
#include <vector>

extern "C" {

// out[i] = FNV-1a 64 of token i, seeded with `basis`, modulo n_features.
// `data` holds the tokens' bytes end to end and lengths[i] token i's bytes.
void pio_text_hash(
    const char* data,
    const int64_t* lengths,
    int64_t n_tokens,
    uint64_t basis,
    uint64_t n_features,
    int64_t* out)
{
    const unsigned char* p = reinterpret_cast<const unsigned char*>(data);
    for (int64_t i = 0; i < n_tokens; ++i) {
        uint64_t h = basis;
        for (const unsigned char* end = p + lengths[i]; p < end; ++p) {
            h = (h ^ *p) * 1099511628211ULL;
        }
        out[i] = static_cast<int64_t>(h % n_features);
    }
}

// The distinct (row, bucket) pairs of the n_buckets buckets, row r owning
// the next sizes[r] of them, with each pair's count: sorted by row, then
// bucket, as numpy's unique over row * n_features + bucket gives them.
// They go into the int32 [3, slots] array `out` (rows, buckets, counts),
// and the slots past them are padding: row `pad_row`, bucket 0, count 0.
// Returns the number of pairs; where it exceeds `slots`, only the first
// `slots` were written and the caller asks again with room for all.
// Returns -1, having written nothing, where the sizes do not add up to
// n_buckets.
int64_t pio_text_pack(
    const int64_t* sizes,
    int64_t n_rows,
    const int64_t* buckets,
    int64_t n_buckets,
    int32_t* out,
    int64_t slots,
    int32_t pad_row)
{
    int64_t total = 0;
    for (int64_t r = 0; r < n_rows; ++r) {
        if (sizes[r] < 0) {
            return -1;
        }
        total += sizes[r];
    }
    if (total != n_buckets) {
        return -1;
    }
    int32_t* row = out;
    int32_t* bucket = out + slots;
    int32_t* count = out + 2 * slots;
    std::vector<int64_t> seg;
    int64_t n = 0;
    for (int64_t r = 0; r < n_rows; ++r) {
        seg.assign(buckets, buckets + sizes[r]);
        buckets += sizes[r];
        std::sort(seg.begin(), seg.end());
        for (size_t i = 0, j; i < seg.size(); i = j) {
            for (j = i + 1; j < seg.size() && seg[j] == seg[i]; ++j) {
            }
            if (n < slots) {
                row[n] = static_cast<int32_t>(r);
                bucket[n] = static_cast<int32_t>(seg[i]);
                count[n] = static_cast<int32_t>(j - i);
            }
            ++n;
        }
    }
    for (int64_t i = n; i < slots; ++i) {
        row[i] = pad_row;
        bucket[i] = 0;
        count[i] = 0;
    }
    return n;
}

}  // extern "C"
